"""The granite-4.0-h-small cell at a tiny size (CPU): the hybrid
reference against the program through prefill and decode, the fp8
control, the FLOP count, and the engine cell end to end with its
per-layer metrics and the ``state_mb`` span attribute."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import run as R
from bench.configs import granite_4h_ref as ref
from bench.peaks import peaks_for
from bench.tests import tiny
from bench.tests.test_bench_reference import _program_logits

CELL = "granite-4h-small.table1"
CONFIG = "granite-4h-small.flat1m-fp32.json"
SEED = 2**31 + 17
# two periods of four: Mamba-2 at 0, 2, 3 and NoPE attention at 1
TINY_HYBRID = {
    "name": "tiny-hybrid", "family": "hybrid", "n_layers": 8, "d_model": 64,
    "n_heads": 4, "n_kv_heads": 2, "head_dim": 16, "d_ff": 32,
    "d_ff_expert": 32, "d_ff_shared": 48, "n_experts": 8, "moe_top_k": 2,
    "moe_every": 1, "experts_held": 4, "experts_first": 0, "vocab_size": 512,
    "rope_theta": None, "attn_scale": 0.1,
    "hybrid_period": 4, "hybrid_attn_index": 1, "ssm_kind": "mamba2",
    "ssm_d_state": 16, "ssm_d_conv": 4, "ssm_expand": 2, "ssm_head_dim": 16,
    "ssm_n_groups": 1, "ssm_chunk": 8,
    "embedding_multiplier": 12.0, "residual_multiplier": 0.22,
    "logits_scaling": 16.0, "tie_embeddings": True, "capacity_factor": 1.5,
    "remat": "none", "norm_eps": 1e-5, "dtype": "bfloat16"}


def _root(tmp_path):
    """The tiny checkout, with this cell's configuration at the tiny
    hybrid size (``tiny.make_root`` gives every model the MoE preset), 8
    new tokens a miss and 4 misses checked. Its logits are ~1/40 of the
    chip model's (÷16, a tied head at d 64), and so is its limit: the
    program's mean gap read 0-5.4e-5 and the fp8 control's 1.6e-4-3.3e-3
    on four seeds. The widest gap keeps the cell's limit: at this size the
    program's (3.4e-5-2.2e-3) and the control's (1.7e-3-9.9e-3) overlap."""
    root = tiny.make_root(tmp_path)
    path = root / "bench" / "configs" / CONFIG
    cfg = json.loads(path.read_text())
    cfg["arch"] = dict(TINY_HYBRID)
    cfg["engine"]["max_new_tokens"] = 8
    cfg["limits"]["logit_gap_mean"] = 1e-4
    path.write_text(json.dumps(cfg))
    path = root / "bench" / "traffic" / "table1-engine-g4h.json"
    mix = json.loads(path.read_text())
    mix["check_requests"] = 4
    path.write_text(json.dumps(mix))
    return root


@pytest.mark.parametrize("n_layers,top_k,groups,held", [
    (8, 8, 2, 0), (4, 2, 1, 0), (4, 3, 1, 3)],
    ids=["two-periods-all-experts", "routed", "expert-share"])
def test_reference_matches_program_prefill_and_decode(n_layers, top_k,
                                                       groups, held):
    """fp32 weights and activations on both sides, the program's prefill
    (the chunked SSD, 12 prompt positions over chunks of 8) then decode
    through its cache against the reference's token-by-token forward. Two
    periods with every expert active and two B/C groups check the
    Mamba-2 mixers, NoPE attention, the multipliers, the tied head and
    the group stacking; top-2 routing checks the routing; top-3 over a
    held share of experts 2-4 checks the share."""
    arch = dict(TINY_HYBRID, dtype="float32", n_layers=n_layers,
                moe_top_k=top_k, ssm_n_groups=groups, experts_held=held,
                experts_first=2 if held else 0)
    params = jax.jit(lambda k: ref.make_weights(arch, k))(jax.random.key(3))
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    seqs = np.random.default_rng(0).integers(
        1, arch["vocab_size"], (3, 17)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        prog = _program_logits(arch, params, seqs, 12)
        mine = np.asarray(ref.reference_logits(arch, params, seqs, 11))
    assert prog.shape == mine.shape == (3, 6, arch["vocab_size"])
    err = np.max(np.abs(prog - mine))
    scale = np.max(np.abs(mine))
    # the program's prefill attention rounds its probabilities to bf16
    # (models/attention.py): ~4e-3 of the logits' scale at most
    assert err <= 1e-2 * scale, (err, scale)


def test_fp8_control_departs_from_the_reference():
    arch = dict(TINY_HYBRID)
    params = jax.jit(lambda k: ref.make_weights(arch, k))(jax.random.key(4))
    seqs = np.random.default_rng(1).integers(
        1, arch["vocab_size"], (4, 24)).astype(np.int32)
    exact = np.asarray(ref.reference_logits(arch, params, seqs, 12))
    low = np.asarray(ref.reference_logits(arch, params, seqs, 12,
                                          low_precision=True))
    assert np.max(np.abs(low - exact)) > 1e-2 * np.max(np.abs(exact))


def test_request_flops_hand_count():
    a = {"d_model": 4, "n_layers": 2, "hybrid_period": 2,
         "hybrid_attn_index": 1, "n_heads": 2, "n_kv_heads": 1,
         "head_dim": 2, "n_experts": 4, "moe_top_k": 2, "experts_held": 2,
         "d_ff_expert": 5, "d_ff_shared": 3, "vocab_size": 7,
         "ssm_expand": 2, "ssm_head_dim": 4, "ssm_n_groups": 1,
         "ssm_d_state": 3, "ssm_d_conv": 2}
    # mamba (di 8, 2 heads of 4, conv width 14): in 2·4·(8+14+2) = 192,
    # conv 2·2·14 = 56, state 5·2·4·3 = 120, out 2·8·4 = 64 -> 432;
    # attention: qkv 2·4·4·2 = 64, out 2·2·2·4 = 32 -> 96;
    # mlp: router 2·4·4 = 32, routed 2·(2/4)·6·4·5 = 120, shared 6·4·3 = 72
    # -> 224 a layer. T = 3 + 2 = 5 tokens: 5·(432 + 96 + 2·224) = 4880;
    # attention over 15 attended positions: 4·2·2·15 = 240; head 3·2·4·7
    assert ref.request_flops(a, 3, 3) == 4880 + 240 + 168


def test_granite_4h_request_flops_is_the_active_share():
    from bench import harness
    cfg = json.loads((harness.BENCH_DIR / "configs" / CONFIG).read_text())
    a = cfg["arch"]
    one = ref.request_flops(a, 1, 1)
    mamba = (2 * 4096 * 16768 + 2 * 4 * 8448 + 5 * 128 * 64 * 128
             + 2 * 8192 * 4096)
    attn = 2 * 4096 * 48 * 128 + 2 * 4096 * 4096 + 4 * 4096
    mlp = 2 * 4096 * 72 + 2.5 * 6 * 4096 * 768 + 6 * 4096 * 1536
    assert one == pytest.approx(9 * mamba + attn + 10 * mlp
                                + 2 * 4096 * 100352)


def test_sound_traced_run_is_correct_and_reads_every_layer(tmp_path):
    """The cell end to end on the engine driver: correct, both of its
    per-layer metrics, and no metric of the other engine cell."""
    from bench import harness as H
    root = _root(tmp_path)
    line, checks = R.run_cell(H.load_cell(CELL, root=root,
                                          bench_dir=root / "bench"),
                              SEED, 4.0, True, tiny.FAKE_DEVICE,
                              peaks_for("TPU v5 lite"), 0.0)
    out = json.loads(line)
    assert out["correct"], checks
    assert checks["tokens_checked"]["value"] > 0
    got = out["metrics"]
    assert got["hybrid_generate_ms"]["value"] > 0
    assert 0 < got["hybrid_mfu"]["value"] < 100
    assert not {"model_mfu", "generate_ms"} & set(got)


def test_fp8_control_reads_far_above_the_program(tmp_path):
    from bench import control
    root = _root(tmp_path)
    r = control.readings(tiny.cell(root, CELL), [SEED], 4.0)[0]
    prog, ctl = r["program"], r["control"]
    assert ctl["logit_gap_mean"] > 3 * prog["logit_gap_mean"]
    assert r["correct"] is True
    assert r["control_correct"] is False
