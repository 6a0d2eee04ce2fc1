"""Per-architecture smoke tests (deliverable f): every assigned arch at
reduced scale — one forward/train step on CPU, shape + finiteness checks,
and decode-vs-prefill consistency."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.models import Model
from repro.models.model import padded_vocab
from repro.models.transformer import layer_groups


def _batch_for(cfg, rng, B=2, S=32):
    batch = {"tokens": jnp.asarray(rng.integers(1, cfg.vocab_size, (B, S))),
             "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)))}
    if cfg.family == "vlm":
        batch["patches"] = jnp.asarray(
            rng.standard_normal((B, cfg.n_patches, cfg.d_model)), jnp.float32)
    if cfg.family == "encdec":
        batch["audio"] = jnp.asarray(
            rng.standard_normal((B, cfg.enc_ctx, cfg.enc_dim)), jnp.float32)
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_arch_smoke_train_step(arch, rng):
    cfg = get_config(arch).reduced()
    model = Model(cfg)
    params = model.init_params(jax.random.key(0))
    batch = _batch_for(cfg, rng)
    loss, met = jax.jit(model.loss_fn)(params, batch)
    assert loss.shape == ()
    assert bool(jnp.isfinite(loss)), f"{arch}: non-finite loss"
    assert float(met["tokens"]) > 0


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_arch_smoke_serve_step(arch, rng):
    cfg = get_config(arch).reduced()
    model = Model(cfg)
    params = model.init_params(jax.random.key(0))
    B, S = 2, 32
    batch = _batch_for(cfg, rng, B, S)
    del batch["labels"]
    logits, cache, kv_len = model.prefill(params, batch, S + 4)
    vp = padded_vocab(cfg.vocab_size)
    assert logits.shape == (B, vp)
    assert bool(jnp.all(jnp.isfinite(logits[:, :cfg.vocab_size])))
    tok = jnp.argmax(logits[:, :cfg.vocab_size], axis=-1).astype(jnp.int32)
    logits2, cache2, kv2 = model.decode_step(params, cache, tok, kv_len)
    assert logits2.shape == (B, vp)
    assert bool(jnp.all(jnp.isfinite(logits2[:, :cfg.vocab_size])))
    assert int(kv2[0]) == int(kv_len[0]) + 1


@pytest.mark.parametrize("arch", ["llama3_2_3b", "gemma2_2b",
                                  "jamba_v0_1_52b", "falcon_mamba_7b",
                                  "whisper_large_v3"])
def test_decode_matches_prefill(arch, rng):
    """Incremental decode of token S−1 == full prefill of S tokens."""
    cfg = get_config(arch).reduced()
    model = Model(cfg)
    params = model.init_params(jax.random.key(1))
    B, S = 2, 24
    toks = rng.integers(1, cfg.vocab_size, (B, S))
    full = _batch_for(cfg, rng, B, S)
    full["tokens"] = jnp.asarray(toks)
    pre = dict(full)
    pre["tokens"] = jnp.asarray(toks[:, :S - 1])
    for b in (full, pre):
        b.pop("labels", None)
    lf, _, _ = model.prefill(params, full, S + 4)
    lp, cache, kvl = model.prefill(params, pre, S + 4)
    ld, _, _ = model.decode_step(params, cache,
                                 jnp.asarray(toks[:, S - 1]), kvl)
    V = cfg.vocab_size
    np.testing.assert_allclose(np.asarray(lf[:, :V]), np.asarray(ld[:, :V]),
                               atol=5e-2)  # bf16 path


def test_layer_groups_patterns():
    """Scan-group factorization matches each family's structure."""
    g, n = layer_groups(get_config("gemma2_2b"))
    assert len(g) == 2 and n == 13
    assert g[0].window == 4096 and g[1].window is None
    g, n = layer_groups(get_config("jamba_v0_1_52b"))
    assert len(g) == 8 and n == 4
    assert [s.kind for s in g].count("attn") == 1
    assert g[4].kind == "attn"
    assert [s.mlp for s in g] == ["dense", "moe"] * 4
    g, n = layer_groups(get_config("falcon_mamba_7b"))
    assert len(g) == 1 and n == 64 and g[0].kind == "mamba"
    g, n = layer_groups(get_config("deepseek_67b"))
    assert len(g) == 1 and n == 95
    g, n = layer_groups(get_config("granite_4_0_h_small"))
    assert len(g) == 10 and n == 4
    assert [s.kind for s in g].count("attn") == 1 and g[5].kind == "attn"
    assert all(s.mlp == "moe" for s in g)


def test_param_counts_plausible():
    """Analytic N close to the marketed sizes (drives MODEL_FLOPS)."""
    expect = {
        "gemma2_2b": (2.0e9, 3.5e9),       # incl. 256k vocab embeddings
        "deepseek_67b": (60e9, 72e9),
        "llama3_2_3b": (2.8e9, 4.0e9),
        "granite_8b": (7.5e9, 9.0e9),
        "kimi_k2_1t_a32b": (0.9e12, 1.15e12),
        "jamba_v0_1_52b": (45e9, 58e9),
        "llava_next_mistral_7b": (6.5e9, 8.0e9),
        "falcon_mamba_7b": (6.5e9, 8.5e9),
        "granite_4_0_h_small": (30e9, 34e9),    # 32B-A9B
    }
    for arch, (lo, hi) in expect.items():
        n = get_config(arch).param_count()
        assert lo <= n <= hi, f"{arch}: {n:.3e} outside [{lo:.2e},{hi:.2e}]"
    # kimi active ≈ 32 B
    a = get_config("kimi_k2_1t_a32b").active_param_count()
    assert 25e9 <= a <= 45e9
    a = get_config("granite_4_0_h_small").active_param_count()
    assert 8e9 <= a <= 10e9


def test_ragged_dot_cotangents_keep_operand_dtypes():
    """The MoE grouped GEMMs accumulate in fp32 from bf16 operands; their
    transpose must hand back bf16 cotangents, or the cotangent sum with
    the bf16 residual/router paths fails to typecheck under grad."""
    from repro.models.moe import _ragged_dot
    lhs = jnp.ones((16, 8), jnp.bfloat16)
    rhs = jnp.ones((2, 8, 4), jnp.bfloat16)
    gs = jnp.array([10, 6], jnp.int32)
    out, vjp = jax.vjp(lambda l, r: _ragged_dot(l, r, gs), lhs, rhs)
    dl, dr = vjp(jnp.ones_like(out))
    assert out.dtype == jnp.float32
    assert dl.dtype == jnp.bfloat16 and dr.dtype == jnp.bfloat16
