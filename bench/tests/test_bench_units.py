"""The benchmark's yardstick on its own: roofline numerators, model FLOPs,
the peaks table and the trace reduction (CPU, no chip)."""

import json

import pytest

from bench import flops, trace_reduce
from bench.configs import granite_moe_ref as ref
from bench.peaks import UnknownDevice, peaks_for

V5E = {"bf16_flops_per_s": 197e12, "int8_ops_per_s": 393e12,
       "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}


def test_flat_topk_work_counts_table_meta_and_queries():
    ops, nbytes = flops.flat_topk_work(1 << 20, 384, 64, "float32")
    assert ops == 2 * 64 * (1 << 20) * 384
    # table 1,610,612,736 + meta row 4,194,304 + queries 98,304
    assert nbytes == 1_610_612_736 + 4_194_304 + 98_304
    ops8, nbytes8 = flops.flat_topk_work(1 << 20, 384, 64, "int8")
    assert ops8 == ops
    # int8 rows, meta row and the scale row
    assert nbytes8 == 402_653_184 + 2 * 4_194_304 + 98_304


def test_scatter_update_work_is_the_rows_payload_read_and_written():
    assert flops.scatter_update_work(10, 384, "float32") == (0.0, 30720.0)


def test_min_seconds_takes_the_binding_roof():
    # HBM-bound: 1.6 GB at 819 GB/s, 51.5 GFLOP at 197 TFLOP/s
    w = flops.flat_topk_work(1 << 20, 384, 64, "float32")
    assert flops.min_seconds(w, V5E) == pytest.approx(w[1] / 819e9)
    assert flops.min_seconds((197e12, 1.0), V5E) == pytest.approx(1.0)


def test_request_flops_hand_count():
    a = {"d_model": 4, "n_layers": 2, "n_heads": 2, "n_kv_heads": 1,
         "head_dim": 2, "n_experts": 3, "moe_top_k": 2, "d_ff_expert": 5,
         "vocab_size": 7}
    # per token per layer: qkv 2·4·(2+2)·2 = 64, out 2·2·2·4 = 32,
    # router 2·4·3 = 24, experts 2 · 3 · 2·4·5 = 240  -> 360
    # prompt 3 + 2 decode tokens = T 5; attended positions 1+..+5 = 15,
    # attention 4·2·2·15 = 240 per layer; head 3 positions · 2·4·7 = 168
    assert ref.request_flops(a, 3, 3) == 2 * (5 * 360 + 240) + 168


def test_granite_request_flops_is_the_active_share():
    from bench import harness
    cfg = json.loads((harness.BENCH_DIR / "configs" /
                      "granite-moe-3b.flat1m-fp32.json").read_text())
    one = ref.request_flops(cfg["arch"], 1, 1)
    # one token attending to itself: 32 layers x 50.5 MFLOP (projections,
    # router, 8 of 40 experts) plus the 151 MFLOP head
    assert one == pytest.approx(32 * 50_454_528 + 4 * 1536 * 32 +
                                2 * 1536 * 49155)


def test_unknown_device_kind_is_an_error():
    assert peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(UnknownDevice):
        peaks_for("TPU v9 imaginary")


def _op(hlo, start, dur):
    return trace_reduce.DeviceOp(hlo, trace_reduce.short_name(hlo), start,
                                 dur)


PALLAS = 'custom_call_target="tpu_custom_call"'


def test_device_ops_are_named_by_their_instruction():
    hlo = ("%flat_topk.1 = (f32[64,1]{1,0}, s32[64,1]{1,0}) custom-call("
           "f32[1048576,384]{1,0} %emb.1), " + PALLAS)
    assert trace_reduce.short_name(hlo) == "flat_topk"
    assert trace_reduce.short_name("%fusion = pred[8]{0} fusion()") == \
        "fusion"
    assert trace_reduce.short_name("copy-start.3") == "copy-start"


def test_trace_reduction_busy_gaps_and_kernels():
    ops = [_op("%fusion.1 = f32[8] fusion()", 0, 100),
           _op(f"%flat_topk.1 = f32[8] custom-call(), {PALLAS}", 50, 150),
           _op(f"%scatter_rows.1 = f32[8] custom-call(), {PALLAS}", 400, 50),
           _op("%flat_topk_prep.2 = f32[8] fusion()", 600, 30),
           _op("%fusion.2 = f32[8] fusion()", 1000, 10)]
    host = [("engine_step", 0, 2000), ("lookup_batch", 300, 100),
            ("arrival_wait", 600, 390)]
    tr = trace_reduce.reduce_trace(ops, host, window_s=2e-6)
    # union: [0, 200) + [400, 450) + [600, 630) + [1000, 1010)
    assert tr["busy_s"] == pytest.approx(290e-9)
    gaps = tr["breakdown"]["idle_gaps"]
    # [630, 1000) mid 815 -> arrival_wait; [200, 400) mid 300 ->
    # lookup_batch, the innermost annotation open there
    assert gaps[0] == ["arrival_wait", pytest.approx(370e-9)]
    assert gaps[1] == ["lookup_batch", pytest.approx(200e-9)]
    top = dict(tr["breakdown"]["device_ops"])
    assert top["flat_topk"] == pytest.approx(150e-9)
    assert top["fusion"] == pytest.approx(110e-9)
    # the kernel by its stable name only: not an XLA fusion that shares
    # a prefix
    assert trace_reduce.kernel_seconds(ops, ("flat_topk",)) == \
        pytest.approx(150e-9)
    assert trace_reduce.kernel_seconds(ops, ("nothing",)) == 0.0


def test_recorded_trace_host_annotations(tmp_path):
    """A trace recorded on this host: the benchmark's annotations are
    found on the host plane with their durations."""
    import jax
    import jax.numpy as jnp

    from bench import harness
    jax.profiler.start_trace(str(tmp_path))
    with harness.annotate("lookup_batch"):
        jnp.ones((64, 64)).block_until_ready()
    jax.profiler.stop_trace()
    tracer = trace_reduce.Tracer(tmp_path)
    ops, host = trace_reduce.read_planes(tracer.xplane())
    names = [h[0] for h in host]
    assert "lookup_batch" in names
    assert all(d >= 0 for _, _, d in host)
