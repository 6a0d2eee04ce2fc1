"""From a profiler trace to device busy time, kernel times and idle gaps.

``Tracer`` profiles a stretch of the window (``jax.profiler``), and
``reduce_trace`` reads the ``.xplane.pb`` it leaves with JAX's own
``ProfileData``: the device's operations (the TPU plane's op line), and
the benchmark's host annotations (``bench.*``, from
``harness.annotate``) on the host plane. Busy time is the union of the
device's operation intervals; an idle gap is time between them, named
by the innermost host annotation open at its middle.
"""

from __future__ import annotations

import glob
import shutil
import time
from dataclasses import dataclass
from pathlib import Path

OPS_LINES = ("XLA Ops",)            # the device plane's per-op line
HOST_PREFIX = "bench."


@dataclass
class DeviceOp:
    label: str          # the op's HLO text plus its string stats
    name: str           # its instruction's name, e.g. "flat_topk"
    start_ns: int
    dur_ns: int


def short_name(hlo: str) -> str:
    """``%flat_topk.1 = (...) custom-call(...)`` -> ``flat_topk``."""
    head = hlo.split(" = ", 1)[0].strip().lstrip("%")
    base, dot, tail = head.rpartition(".")
    return base if dot and tail.isdigit() else head


class Tracer:
    """Profiles ``[t0 + start_frac·seconds, + trace_s)`` of a window. The
    window loop calls ``poll`` as it goes; ``finish`` stops a trace still
    running."""

    def __init__(self, out_dir: Path, start_frac: float = 0.3,
                 trace_s: float = 4.0):
        self.out_dir = Path(out_dir)
        self.start_frac, self.trace_s = start_frac, trace_s
        self.host_start = self.host_stop = None
        self._t_on = self._t_off = None

    def arm(self, t0: float, seconds: float) -> None:
        self._t_on = t0 + self.start_frac * seconds
        self._t_off = self._t_on + min(self.trace_s, 0.5 * seconds)

    def poll(self, now: float) -> None:
        import jax
        if self.host_start is None and now >= self._t_on:
            shutil.rmtree(self.out_dir, ignore_errors=True)
            jax.profiler.start_trace(str(self.out_dir))
            self.host_start = time.monotonic()
        elif self.host_start is not None and self.host_stop is None \
                and now >= self._t_off:
            self._stop()

    def _stop(self) -> None:
        import jax
        self.host_stop = time.monotonic()
        jax.profiler.stop_trace()

    def finish(self) -> None:
        if self.host_start is not None and self.host_stop is None:
            self._stop()

    def xplane(self) -> Path | None:
        files = sorted(glob.glob(str(self.out_dir / "**" / "*.xplane.pb"),
                                 recursive=True))
        return Path(files[-1]) if files else None


def _label(ev) -> str:
    parts = [ev.name]
    for k, v in ev.stats:
        if isinstance(v, str):
            parts.append(v)
    return " ".join(parts)


def read_planes(path: Path):
    """(device ops, host annotations) from one trace file. Host
    annotations are (name, start_ns, dur_ns)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    ops: list[DeviceOp] = []
    host: list[tuple[str, int, int]] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:0"):
            for line in plane.lines:
                if line.name in OPS_LINES:
                    ops.extend(DeviceOp(_label(e), short_name(e.name),
                                        int(e.start_ns), int(e.duration_ns))
                               for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        host.append((e.name[len(HOST_PREFIX):],
                                     int(e.start_ns), int(e.duration_ns)))
    return ops, host


def busy_intervals(ops: list[DeviceOp]) -> list[tuple[int, int]]:
    """Union of the ops' [start, end) intervals, sorted."""
    iv = sorted((o.start_ns, o.start_ns + o.dur_ns) for o in ops)
    out: list[list[int]] = []
    for s, e in iv:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def host_activity(host: list[tuple[str, int, int]], t_ns: int) -> str:
    """The innermost benchmark annotation open at ``t_ns``."""
    best, best_dur = "none", None
    for name, s, d in host:
        if s <= t_ns < s + d and (best_dur is None or d < best_dur):
            best, best_dur = name, d
    return best


def reduce_trace(ops: list[DeviceOp], host: list[tuple[str, int, int]],
                 window_s: float, top: int = 10) -> dict:
    """Busy seconds, the longest device ops by total time, and the
    longest idle gaps named by what the host was doing."""
    busy = busy_intervals(ops)
    busy_s = sum(e - s for s, e in busy) / 1e9
    by_name: dict[str, int] = {}
    for o in ops:
        by_name[o.name] = by_name.get(o.name, 0) + o.dur_ns
    device_ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    gaps = [(busy[k][1], busy[k + 1][0]) for k in range(len(busy) - 1)]
    gaps.sort(key=lambda g: g[0] - g[1])
    idle = [[host_activity(host, (s + e) // 2), (e - s) / 1e9]
            for s, e in gaps[:top]]
    return {"busy_s": busy_s, "window_s": window_s,
            "breakdown": {"device_ops": [[n, d / 1e9] for n, d in
                                         device_ops],
                          "idle_gaps": idle}}


def kernel_seconds(ops: list[DeviceOp], names: tuple[str, ...]) -> float:
    """Total device time of the Pallas kernels (``tpu_custom_call``) whose
    instruction carries one of ``names``, a kernel's stable name."""
    return sum(o.dur_ns for o in ops
               if o.name in names and "tpu_custom_call" in o.label) / 1e9
