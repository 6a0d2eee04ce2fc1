"""``bench/span_report.py``: idle gaps named by the innermost program
span, idle time split by stage, and self times, on planted timelines and
on a recorded CPU trace."""

import time

import numpy as np
import pytest

from bench import span_report as S
from bench.trace_reduce import DeviceOp


def _op(s, e, name="fusion"):
    return DeviceOp(name, name, s, e - s)


def test_planted_gap_is_named_by_its_innermost_program_span():
    ops = [_op(0, 10), _op(50, 60), _op(100, 120)]
    bench = [("lookup_batch", 0, 70), ("insert_batch", 70, 40)]
    prog = [("lookup", 5, 60, {"batch": 4}),
            ("search", 8, 52, {"batch": 4}),
            ("device_wait", 40, 18, {"batch": 4}),
            ("insert", 72, 30, {"batch": 2}),
            ("evict", 80, 10, {"batch": 2})]
    r = S.report(ops, bench, prog)
    assert r["busy_ms"] == pytest.approx(40e-6)
    # gaps [10, 50) (middle 30: search) and [60, 100) (middle 80: evict)
    assert sorted(r["longest_gaps"]) == [["evict", 40e-6],
                                         ["search", 40e-6]]
    idle = r["idle_ms_by_stage"]
    assert idle["search"] == pytest.approx(30e-6)        # [10, 40)
    assert idle["device_wait"] == pytest.approx(10e-6)   # [40, 50)
    assert idle["lookup"] == pytest.approx(5e-6)         # [60, 65)
    assert idle["bench.lookup_batch"] == pytest.approx(5e-6)  # [65, 70)
    assert idle["bench.insert_batch"] == pytest.approx(2e-6)  # [70, 72)
    assert idle["insert"] == pytest.approx(18e-6)   # [72, 80) + [90, 100)
    assert idle["evict"] == pytest.approx(10e-6)
    assert sum(idle.values()) == pytest.approx(r["idle_ms"])
    st = r["stages"]
    assert st["search"]["mean_ms"] == pytest.approx(52e-6)
    assert st["search"]["mean_self_ms"] == pytest.approx(34e-6)
    assert st["lookup"]["mean_self_ms"] == pytest.approx(8e-6)
    assert st["insert"]["mean_self_ms"] == pytest.approx(20e-6)
    assert st["evict"]["attrs"] == {"batch": 2}


def test_gap_under_no_span_falls_back_to_none():
    r = S.report([_op(0, 10), _op(30, 40)], [], [])
    assert r["longest_gaps"] == [["none", 20e-6]]
    assert r["stages"] == {}


def test_recorded_cpu_trace_names_the_planted_gap(tmp_path):
    """A real profiler trace: the host sleeps inside an ``evict`` span
    between two programs; the CPU's op events stand in for the device's."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    from repro.core.clock import WallClock
    from repro.obs import TraceRecorder
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    rec = TraceRecorder(WallClock())
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.insert_batch"):
            with rec.span("insert", batch=3):
                f(x).block_until_ready()
                with rec.span("evict", batch=3):
                    time.sleep(0.05)
                f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path = S.find_xplane(tmp_path)
    prog = S.read_program_spans(path)
    assert [p[0] for p in prog] == ["insert", "evict"]
    assert prog[1][3] == {"batch": 3}
    ops = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            ops += [_op(int(e.start_ns), int(e.start_ns + e.duration_ns),
                        e.name) for e in line.events
                    if any(k == "hlo_op" for k, _ in e.stats)]
    r = S.report(ops, [], prog)
    name, ms = r["longest_gaps"][0]
    assert name == "evict" and ms >= 50
    assert r["idle_ms_by_stage"]["evict"] >= 50
    assert r["stages"]["insert"]["mean_self_ms"] < \
        r["stages"]["insert"]["mean_ms"] - 50
    assert np.isfinite(r["stages"]["evict"]["mean_self_ms"])
