"""The on-chip benchmark.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` in this process on the chip JAX finds
(there is no CPU fallback: anything but enough TPU chips exits non-zero
with no result). Set-up (weights, fill, warm-up of every shape the window
uses) counts as ``setup_s``; then the window runs for ``--seconds``, open
loop. With ``--trace 0`` the result holds the cell's end-to-end metrics;
with ``--trace 1`` a stretch of the window is profiled and the result
holds its per-layer metrics, the device's busy seconds and a breakdown.
Either way the window's answers are checked against the plain
references, and each number compared is printed beside its limit: as the
last lines of standard error and under ``checks``, last in the result.

Earlier lines: ``[device]`` (platform, kind, count), ``[window]``
(compilations inside the window, how late the arrival generator woke).
The last line of standard output is the result, one JSON object.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness as H  # noqa: E402
from bench import trace_reduce  # noqa: E402
from bench.peaks import peaks_for  # noqa: E402

TRACE_DIR = ROOT / ".bench_trace"


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def per_layer(cell: H.Cell, ctx: dict) -> dict:
    """Each per-layer metric of the cell, by its own reader; a reader that
    finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in cell.per_layer:
        v = H.metric_reader(m["name"])(ctx)
        if v is not None and math.isfinite(v):
            out[m["name"]] = v
    return out


def window_spans(obs, w: H.Window) -> dict[str, list[float]]:
    """Program span durations (ms) by stage, for spans begun in the
    window."""
    out: dict[str, list[float]] = {}
    for sp in obs.spans:
        if w.t0 <= sp.t0 < w.t_end and sp.dur_ms is not None:
            out.setdefault(sp.stage, []).append(sp.dur_ms)
    return out


def run_cell(cell: H.Cell, seed: int, seconds: float, trace: bool,
             device: dict, peaks: dict, t_start: float) -> tuple[str, dict]:
    """Drive one run of a cell; returns (result line, checks). The caller
    has established the device."""
    drv = H.driver_for(cell)
    obs = tracer = None
    if trace:
        from repro.core.clock import WallClock
        from repro.obs import TraceRecorder
        obs = TraceRecorder(WallClock())
        tracer = trace_reduce.Tracer(TRACE_DIR)
    w, checks, layer = drv.run(cell, seed, seconds, tracer=tracer, obs=obs)
    setup_s = w.t0 - t_start
    print("[window] " + json.dumps(H.lateness_line(w)), flush=True)
    # after the window: the untimed drain, then the reference's checks
    print("[phases] " + json.dumps({
        "setup_s": setup_s, "window_s": w.seconds,
        "after_window_s": time.monotonic() - w.t_end}), flush=True)
    device = dict(device, memory_peak_bytes=layer["memory_peak_bytes"])
    correct = H.is_correct(checks)
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    breakdown = None
    if not trace:
        e2e = H.end_to_end(w, setup_s)
        metrics = {m["name"]: e2e[m["name"]] for m in cell.end_to_end}
    else:
        tr = None
        path = tracer.xplane()
        if path is not None:
            ops, host = trace_reduce.read_planes(path)
            tr = trace_reduce.reduce_trace(
                ops, host, tracer.host_stop - tracer.host_start)
            tr.update(ops=ops, host_start=tracer.host_start,
                      host_stop=tracer.host_stop)
            breakdown = tr["breakdown"]
            device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        ctx = drv.layer_context(layer, tr, peaks, cell)
        ctx["spans"] = window_spans(obs, w)
        metrics = per_layer(cell, ctx)
    attempted = len(w.due)
    # wrong answers found, plus one for each other check that failed
    failed = sum(int(c["value"]) if k.endswith("_mismatches") else 1
                 for k, c in checks.items() if not c.get("ok", True))
    line = H.result_line(correct=correct, attempted=attempted,
                         failed=failed, metrics=metrics, units=units,
                         device=device, checks=checks, breakdown=breakdown)
    return line, checks


def main(argv=None) -> int:
    args = parse(argv)
    try:
        cell = H.load_cell(args.workload)
    except H.SpecError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    try:
        device = H.device_info(int(cell.entry["chips"]))
    except H.NoAccelerator as e:
        print(f"bench: {e}; there is no CPU fallback", file=sys.stderr)
        return 3
    print("[device] " + json.dumps(device), flush=True)
    peaks = peaks_for(device["kind"])

    import jax
    from repro.launch.compile_cache import configure_compile_cache
    cache_dir = configure_compile_cache()
    # every program, however quick to compile, goes to the cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    print(f"[compile_cache] {cache_dir}", flush=True)

    line, checks = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                            device, peaks, T_START)
    for name, c in checks.items():
        if "limit" in c:
            print(f"check {name} = {c['value']} (limit {c['limit']})",
                  file=sys.stderr)
    sys.stderr.flush()
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
