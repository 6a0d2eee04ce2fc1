"""What every cell shares: the spec files, the device, the compile census,
host annotations, latency statistics and the result line.

A cell is an entry of ``BENCHMARK.json``'s ``workloads``. Its
configuration is ``configs/<config>.json``, whose ``driver`` names the
module in ``drivers/`` that runs it; its traffic is
``traffic/<traffic>.json``; each per-layer metric is read by
``metrics/<name>.py``. All are found by name: a new cell, mix or metric
is new files and new entries, never an edit.
"""

from __future__ import annotations

import importlib
import json
import logging
import math
import time
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


class SpecError(ValueError):
    pass


@dataclass
class Cell:
    name: str
    entry: dict                     # the workloads entry
    config: dict                    # configs/<config>.json
    traffic: dict                   # traffic/<traffic>.json
    end_to_end: list[dict]          # metrics this cell reports, trace 0
    per_layer: list[dict]           # metrics this cell reports, trace 1
    bench_dir: Path = BENCH_DIR


def load_json(path: Path) -> dict:
    try:
        return json.loads(Path(path).read_text())
    except FileNotFoundError as e:
        raise SpecError(f"missing file {path}") from e


def load_cell(name: str, root: Path = ROOT,
              bench_dir: Path = BENCH_DIR) -> Cell:
    """Resolve a cell by name from ``BENCHMARK.json`` under ``root``."""
    spec = load_json(Path(root) / "BENCHMARK.json")
    entry = next((w for w in spec["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json")
    cfg_entry = next((c for c in spec["configs"]
                      if c["name"] == entry["config"]), None)
    if cfg_entry is None:
        raise SpecError(f"workload {name!r}: no config {entry['config']!r}")
    config = load_json(Path(root) / cfg_entry["file"])
    traffic = load_json(bench_dir / "traffic" / f"{entry['traffic']}.json")
    e2e = [m for m in spec["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in reported)]
    return Cell(name, entry, config, traffic, e2e, per_layer, bench_dir)


def load_module(relpath: str):
    """Import a file of the benchmark, named by its path from the root
    (``bench/drivers/cache.py``), as the module ``bench.drivers.cache``."""
    if not (ROOT / relpath).exists():
        raise SpecError(f"missing file {relpath}")
    return importlib.import_module(
        relpath.removesuffix(".py").replace("/", "."))


def driver_for(cell: Cell):
    return load_module(f"bench/drivers/{cell.config['driver']}.py")


def metric_reader(name: str):
    return load_module(f"bench/metrics/{name}.py").read


# --------------------------------------------------------------- the device

class NoAccelerator(RuntimeError):
    pass


def device_info(chips: int) -> dict:
    """JAX's platform, kind and count; anything but enough TPU chips is
    an error (no CPU fallback)."""
    import jax
    devs = jax.devices()
    d = devs[0]
    info = {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}
    if d.platform != "tpu":
        raise NoAccelerator(f"JAX found platform {d.platform!r} "
                            f"({d.device_kind}), not a TPU")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX sees "
                            f"{len(devs)}")
    return info


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest chip."""
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))


class Census:
    """Backend compilations (JAX's monitoring events) while active:
    inside a window there should be none."""

    def __init__(self):
        self.compiles = 0

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        return self

    def __exit__(self, *_):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        return False


class GcPauses:
    """Python's garbage collections while active, by generation, with
    their pauses: a full (generation 2) collection over the cache's
    million host objects stalls every request behind it."""

    def __init__(self):
        self.count = [0, 0, 0]
        self.pause_s: list[float] = []
        self._t = 0.0

    def _on_gc(self, phase, info):
        if phase == "start":
            self._t = time.monotonic()
        else:
            self.count[info["generation"]] += 1
            self.pause_s.append(time.monotonic() - self._t)

    def __enter__(self):
        import gc
        gc.callbacks.append(self._on_gc)
        return self

    def __exit__(self, *_):
        import gc
        gc.callbacks.remove(self._on_gc)
        return False


class Lowerings(logging.Handler):
    """Programs JAX lowers in this process while active (new jit cache
    entries, whether then compiled or loaded from the persistent cache):
    inside a window there should be none."""

    LOGGER = "jax._src.interpreters.pxla"

    def __init__(self):
        super().__init__(logging.DEBUG)
        self.names: list[str] = []

    def emit(self, record):
        if str(record.msg).startswith("Compiling") and record.args:
            shapes = str(record.args[1]) if len(record.args) > 1 else ""
            self.names.append(f"{record.args[0]} {shapes[:120]}")

    def __enter__(self):
        lg = logging.getLogger(self.LOGGER)
        self._saved = lg.level, lg.propagate
        lg.setLevel(logging.DEBUG)
        lg.propagate = False
        lg.addHandler(self)
        return self

    def __exit__(self, *_):
        lg = logging.getLogger(self.LOGGER)
        lg.removeHandler(self)
        lg.setLevel(self._saved[0])
        lg.propagate = self._saved[1]
        return False


def annotate(name: str):
    """A host span on the profiler's timeline (``bench.<name>``): what the
    host was doing while the device idled."""
    import jax
    return jax.profiler.TraceAnnotation("bench." + name)


# ------------------------------------------------------------ the window

@dataclass
class Window:
    """Per-request outcome of one measured window (host clock)."""
    t0: float
    seconds: float
    due: np.ndarray                          # absolute due times
    done: np.ndarray = None                  # absolute answer times (nan)
    hit: np.ndarray = None                   # bool
    answered: np.ndarray = None              # bool: answered in window
    late_s: list = field(default_factory=list)   # generator lateness
    compiles: int = 0
    lowered: list = field(default_factory=list)  # programs new in window
    gc: GcPauses = None                      # collections in the window

    def __post_init__(self):
        n = len(self.due)
        if self.done is None:
            self.done = np.full(n, np.nan)
            self.hit = np.zeros(n, bool)
            self.answered = np.zeros(n, bool)

    @property
    def t_end(self) -> float:
        return self.t0 + self.seconds


def p95(x: np.ndarray) -> float:
    """95th percentile (linear interpolation), nan for no samples."""
    return float(np.percentile(x, 95)) if len(x) else math.nan


def end_to_end(w: Window, setup_s: float) -> dict[str, float]:
    """The cell's end-to-end metrics from its window. A request answered
    after the window's end counts in its tail at the window's end."""
    lat_ms = (np.minimum(w.done, w.t_end) - w.due) * 1e3
    hits = w.hit
    return {
        "hit_p95_ms": p95(lat_ms[hits]),
        "miss_p95_ms": p95(lat_ms[~hits]),
        "served_rps": float(np.sum(w.answered)) / w.seconds,
        "hit_rate": float(np.mean(hits)) if len(hits) else math.nan,
        "setup_s": setup_s,
    }


def lateness_line(w: Window) -> dict:
    late = np.asarray(w.late_s) * 1e3
    return {"compiles_in_window": w.compiles,
            "programs_lowered_in_window": len(w.lowered),
            "lowered": w.lowered[:8],
            "generator_wakeups": int(late.size),
            "generator_late_ms_p50": float(np.median(late)) if late.size
            else 0.0,
            "generator_late_ms_max": float(np.max(late)) if late.size
            else 0.0,
            "gc_collections_by_generation": w.gc.count if w.gc else None,
            "gc_pause_ms_max": 1e3 * max(w.gc.pause_s, default=0.0)
            if w.gc else None}


def wait_until(t: float, w: Window) -> None:
    """Sleep until host time ``t`` (an arrival or a write-back is due),
    recording how late the wake-up came."""
    now = time.monotonic()
    if t <= now:
        return
    with annotate("arrival_wait"):
        time.sleep(t - now)
    w.late_s.append(time.monotonic() - t)


# ------------------------------------------------------------ the result

def check_entry(value, limit, ok: bool) -> dict:
    return {"value": value, "limit": limit, "ok": bool(ok)}


def is_correct(checks: dict) -> bool:
    """Every number compared lies within its limit."""
    return all(c.get("ok", True) for c in checks.values())


def result_line(*, correct: bool, attempted: int, failed: int,
                metrics: dict, units: dict, device: dict,
                checks: dict, breakdown: dict | None = None) -> str:
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed),
           "metrics": {k: {"value": v, "unit": units[k]}
                       for k, v in metrics.items()},
           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return json.dumps(out)


def kernel_context(layer: dict, trace: dict | None, peaks: dict) -> dict:
    """What the cache-kernel readers read: the required work of each
    lookup made wholly inside the traced stretch (its scan and the delta
    flush it carried), and the counters of the window."""
    from bench import flops
    ctx = {"memory_peak_bytes": layer["memory_peak_bytes"], "peaks": peaks,
           "trace": trace, "counters": layer["counters"]}
    if trace is None:
        return ctx
    lo, hi = trace["host_start"], trace["host_stop"]
    inside = [c for c in layer["lookup_calls"] if lo <= c[0] and c[1] <= hi]
    n, d, dt = layer["n_rows"], layer["dim"], layer["emb_dtype"]
    scan = [flops.flat_topk_work(n, d, b, dt) for _, _, b, _ in inside]
    ctx["work"] = {
        "flat_topk": scan,
        "scatter_update": [flops.scatter_update_work(r, d, dt)
                           for _, _, _, r in inside if r > 0],
    }
    return ctx
