"""Mamba-2 (SSD) mixer, the expert share, the granite multipliers and the
``state_mb`` span attribute, at tiny sizes on the CPU (seeded)."""

from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import mamba2 as m2
from repro.models import moe as moe_mod
from repro.models.layers import dtype_of, init_moe, swiglu


def _hybrid(**kw):
    return get_config("granite_4_0_h_small").reduced(dtype="float32", **kw)


def _ssd_inputs(rng, B=2, L=37, G=2, Hg=3, P=4, N=5):
    f = np.float32
    x = rng.standard_normal((B, L, G, Hg, P)).astype(f)
    dt = np.exp(rng.uniform(np.log(1e-2), np.log(0.5), (B, L, G, Hg))
                ).astype(f)
    A = -rng.uniform(1.0, 8.0, (G, Hg)).astype(f)
    Bm = rng.standard_normal((B, L, G, N)).astype(f)
    Cm = rng.standard_normal((B, L, G, N)).astype(f)
    h0 = rng.standard_normal((B, G, Hg, P, N)).astype(f)
    return tuple(map(jnp.asarray, (x, dt, A, Bm, Cm, h0)))


@pytest.mark.parametrize("L,chunk", [(37, 8), (32, 8), (5, 8)],
                         ids=["tail", "whole-chunks", "one-short-chunk"])
def test_chunked_ssd_matches_step_recurrence(L, chunk):
    """The chunked form (quadratic within a chunk, state carried between
    chunks) against the one-step recurrence, from a non-zero state: every
    output and the final state, over sequences of several chunks with and
    without a short tail."""
    x, dt, A, Bm, Cm, h0 = _ssd_inputs(np.random.default_rng(L), L=L)
    with jax.default_matmul_precision("highest"):
        y, h = m2.ssd_chunked(x, dt, A, Bm, Cm, h0, chunk)
        hs, ys = h0, []
        for t in range(L):
            yt, hs = m2.ssd_step(x[:, t], dt[:, t], A, Bm[:, t], Cm[:, t], hs)
            ys.append(yt)
    np.testing.assert_allclose(np.asarray(y), np.stack(ys, 1), rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hs), rtol=1e-4,
                               atol=1e-4)


def test_mamba2_prefill_then_decode_matches_whole_sequence():
    """The block (conv window and SSD state carried in the cache): a
    prefill of 13 positions then 8 one-token decodes give the outputs of
    one chunked pass over all 21, and the same final state."""
    cfg = _hybrid(ssm_chunk=8, ssm_n_groups=2)
    p = m2.init_mamba2(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (2, 21, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        whole, s_whole = m2.mamba2_block(x, p, cfg)
        zero = m2.init_mamba2_state(cfg, 2, jnp.float32)
        out, s = m2.mamba2_block(x[:, :13], p, cfg, state=zero)
        outs = [out]
        for t in range(13, 21):
            o, s = m2.mamba2_block(x[:, t:t + 1], p, cfg, state=s,
                                   decode=True)
            outs.append(o)
    np.testing.assert_allclose(np.concatenate(outs, 1), np.asarray(whole),
                               rtol=2e-4, atol=2e-4)
    for k in ("h", "conv"):
        np.testing.assert_allclose(np.asarray(s[k]), np.asarray(s_whole[k]),
                                   rtol=2e-4, atol=2e-4)
    assert s["h"].dtype == jnp.float32
    assert s["h"].shape == (2, cfg.ssm_n_heads, cfg.ssm_head_dim,
                            cfg.ssm_d_state)


def test_served_model_carries_the_state_in_float32():
    """The bf16 model as served keeps every Mamba-2 state in float32
    through prefill and decode: the benchmark's logit gaps cannot see a
    bf16 state (the bf16 weights' rounding dominates them), so this is
    the check that the stated state precision holds."""
    from repro.models import Model
    cfg = get_config("granite_4_0_h_small").reduced()
    model = Model(cfg)
    params = model.init_params(jax.random.key(0))
    tokens = jnp.arange(2 * 12, dtype=jnp.int32).reshape(2, 12) % 64
    _, cache, kv_len = model.prefill(params, {"tokens": tokens}, 16)
    _, cache, _ = model.decode_step(params, cache, tokens[:, -1], kv_len)
    states = [leaf for path, leaf in
              jax.tree_util.tree_leaves_with_path(cache)
              if getattr(path[-1], "key", None) == "h"]
    assert dtype_of(cfg) == jnp.bfloat16 and states
    assert all(h.dtype == jnp.float32 for h in states)


def test_expert_shares_sum_to_the_uncut_layer():
    """Four shares of two experts each, routed over all eight: their
    partial outputs, with the shared expert (which every share computes
    alike) counted once, add up to the layer that holds every expert."""
    cfg = replace(_hybrid(), n_experts=8, moe_top_k=3)
    p = init_moe(jax.random.key(2), cfg)
    x = jax.random.normal(jax.random.key(3), (24, cfg.d_model))
    s = p["shared"]
    with jax.default_matmul_precision("highest"):
        whole, _ = moe_mod.moe_apply(x, p, cfg, None)
        shared = swiglu(x, s["w_gate"], s["w_up"], s["w_down"])
        parts = []
        for first in range(0, 8, 2):
            share = dict(p, **{k: p[k][first:first + 2]
                               for k in ("w_gate", "w_up", "w_down")})
            c = replace(cfg, experts_held=2, experts_first=first)
            parts.append(moe_mod.moe_apply(x, share, c, None)[0])
    total = sum(parts) - (len(parts) - 1) * shared
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=1e-4, atol=1e-5)
    # a share is a part: it leaves out what the other experts add
    assert float(jnp.max(jnp.abs(parts[0] - whole))) > 1e-3


def test_ep_path_refuses_a_held_share():
    from repro.distributed.context import Dist
    cfg = replace(_hybrid(), experts_held=2)
    with pytest.raises(ValueError, match="held share"):
        moe_mod.moe_ffn_ep(jnp.zeros((4, cfg.d_model)),
                           init_moe(jax.random.key(0), cfg), cfg,
                           Dist(mesh=None))


def test_granite_multipliers_tie_and_nope():
    """The published multipliers act where granite applies them: the tied
    head reads the embedding table, logits are divided by
    ``logits_scaling``, and NoPE attention carries no position."""
    from repro.models import Model
    cfg = _hybrid()
    model = Model(cfg)
    params = model.init_params(jax.random.key(0))
    assert "head" not in params
    toks = jnp.asarray(np.random.default_rng(0).integers(1, 512, (2, 10)))
    logits, _, _ = model.prefill(params, {"tokens": toks}, 12)
    unscaled, _, _ = Model(replace(cfg, logits_scaling=1.0)).prefill(
        params, {"tokens": toks}, 12)
    np.testing.assert_allclose(np.asarray(logits[:, :512]) * 16.0,
                               np.asarray(unscaled[:, :512]), rtol=1e-5,
                               atol=1e-5)
    # NoPE: the last position's attention reads its context as a set, so
    # reordering the earlier tokens leaves it unchanged; rotary does not
    from repro.models import transformer as tf
    from repro.models.layers import init_attention
    sp = {"ln_mix": jnp.zeros((cfg.d_model,)),
          "mix": init_attention(jax.random.key(5), cfg)}
    spec = tf.SubLayerSpec(kind="attn", mlp="none")
    x = jax.random.normal(jax.random.key(6), (1, 6, cfg.d_model))
    x_perm = x[:, jnp.array([3, 0, 4, 1, 2, 5])]
    for theta, same in ((None, True), (10000.0, False)):
        c = replace(cfg, rope_theta=theta)
        out = [tf.attn_sublayer(v, sp, c, spec, mode="train",
                                positions=jnp.arange(6))[0][0, -1]
               for v in (x, x_perm)]
        assert np.allclose(out[0], out[1], atol=1e-5) == same, theta


def test_jamba_attention_has_no_positional_encoding():
    assert get_config("jamba_v0_1_52b").rope_theta is None


def test_model_generate_carries_state_mb():
    """The engine's ``model_generate`` span gives the MiB of recurrent and
    KV state its bucket carries, from the cache pytree's shapes: here 8
    rows of 9 Mamba-2 layers ((4, 64, 8) fp32 state, a (3, 272) bf16
    conv window) and one layer's K and V of 24 positions."""
    from repro.core import SemanticCache, SimClock
    from repro.core.policy import PolicyEngine, paper_policies
    from repro.models import Model
    from repro.obs import TraceRecorder
    from repro.serving.engine import ServingEngine
    cfg = get_config("granite_4_0_h_small").reduced()
    clock = SimClock()
    rec = TraceRecorder(clock)
    cache = SemanticCache(PolicyEngine(paper_policies()), capacity=64,
                          clock=clock, index_kind="flat")
    eng = ServingEngine(Model(cfg), None, cache, max_batch=8, prompt_len=16,
                        max_new_tokens=8, obs=rec)
    eng._generate = lambda p, t: np.zeros((t.shape[0], 8), np.int32)
    for k in range(3):
        eng.submit(f"a distinct question {k}", "code_generation",
                   np.arange(2, 9))
    eng.step()
    gen = [sp for sp in rec.spans if sp.stage == "model_generate"]
    ssm = 9 * 8 * (4 * 64 * 8 * 4 + 3 * 272 * 2)
    kv = 2 * 8 * 24 * 2 * 32 * 2
    assert gen and gen[0].attrs["bucket"] == 8
    assert gen[0].attrs["state_mb"] == pytest.approx((ssm + kv) / 2**20)
