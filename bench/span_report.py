"""The device's idle time by program stage, from one profiler trace.

    python3 bench/span_report.py <trace.xplane.pb | trace dir> [--top N] [--json]

Reads the program's own spans (``repro.<stage>`` host events, which
``repro.obs.TraceRecorder`` opens beside each span) and the device's
operations from a trace a ``--trace 1`` run of ``bench/run.py`` leaves
(``.bench_trace/``), and prints:

- the longest idle gaps of the device, each named by the innermost
  program span open at its middle (else the benchmark's ``bench.*``
  annotation, else ``none``);
- idle time per stage: each gap split at the spans' and annotations'
  edges, each piece named as a gap is;
- per stage: count, mean total and mean self time (total less the time
  of the program spans nested in it), on the profiler's clock, and the
  means of the spans' numeric attributes.

Busy time and gaps are those of ``trace_reduce`` (gaps between the
device's busy intervals).
"""

from __future__ import annotations

import argparse
import bisect
import glob
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import trace_reduce  # noqa: E402

PROGRAM_PREFIX = "repro."


def find_xplane(path: Path) -> Path:
    path = Path(path)
    if path.is_file():
        return path
    files = sorted(glob.glob(str(path / "**" / "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return Path(files[-1])


def read_program_spans(path: Path) -> list[tuple[str, int, int, dict]]:
    """The program's spans in a trace: (stage, start_ns, dur_ns, numeric
    attributes), sorted by start."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PROGRAM_PREFIX):
                    attrs = {k: v for k, v in e.stats
                             if isinstance(v, (int, float))
                             and not k.startswith("_")}
                    out.append((e.name[len(PROGRAM_PREFIX):],
                                int(e.start_ns), int(e.duration_ns), attrs))
    out.sort(key=lambda s: (s[1], -s[2]))
    return out


class _Open:
    """Which spans are open at a time: candidates by start (bisect) and
    the longest duration bound the search."""

    def __init__(self, spans: list[tuple[str, int, int]]):
        self.spans = sorted(spans, key=lambda s: s[1])
        self.starts = [s for _, s, _ in self.spans]
        self.max_dur = max((d for _, _, d in self.spans), default=0)

    def near(self, lo: int, hi: int) -> list[tuple[str, int, int]]:
        """Spans that may overlap [lo, hi)."""
        a = bisect.bisect_left(self.starts, lo - self.max_dur)
        b = bisect.bisect_right(self.starts, hi)
        return [sp for sp in self.spans[a:b] if sp[1] + sp[2] > lo]

    def innermost(self, t: int) -> str:
        return trace_reduce.host_activity(self.near(t, t), t)


def idle_gaps(ops) -> list[tuple[int, int]]:
    busy = trace_reduce.busy_intervals(ops)
    return [(busy[k][1], busy[k + 1][0]) for k in range(len(busy) - 1)]


def report(ops, bench_host, program, top: int = 10) -> dict:
    """The report of one trace: ``ops`` the device's operations,
    ``bench_host`` the ``bench.*`` annotations (name, start, dur),
    ``program`` the program's spans (stage, start, dur, attrs)."""
    prog = _Open([(st, s, d) for st, s, d, _ in program])
    bench = _Open(list(bench_host))

    def name_at(t: int) -> str:
        n = prog.innermost(t)
        if n == "none":
            b = bench.innermost(t)
            n = "none" if b == "none" else "bench." + b
        return n

    gaps = idle_gaps(ops)
    idle_by: dict[str, float] = {}
    for s, e in gaps:
        cuts = {s, e}
        for _, ps, pd in prog.near(s, e) + bench.near(s, e):
            cuts.update(t for t in (ps, ps + pd) if s < t < e)
        cuts = sorted(cuts)
        for a, b in zip(cuts, cuts[1:]):
            n = name_at((a + b) // 2)
            idle_by[n] = idle_by.get(n, 0.0) + (b - a) / 1e6
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    busy_ns = sum(e - s for s, e in trace_reduce.busy_intervals(ops))
    return {"busy_ms": busy_ns / 1e6,
            "idle_ms": sum(e - s for s, e in gaps) / 1e6,
            "longest_gaps": [[name_at((s + e) // 2), (e - s) / 1e6]
                             for s, e in longest],
            "idle_ms_by_stage": dict(sorted(idle_by.items(),
                                            key=lambda kv: -kv[1])),
            "stages": stage_times(program)}


def stage_times(program) -> dict:
    """Per stage: count, mean total and mean self ms, attribute means.
    A span's children are the program spans that start inside it while it
    is the innermost open (spans of one thread nest)."""
    child_ns = [0] * len(program)
    stack: list[int] = []
    for i, (_, s, d, _) in enumerate(program):
        while stack and program[stack[-1]][1] + program[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            child_ns[stack[-1]] += d
        stack.append(i)
    out: dict[str, dict] = {}
    for (stage, _, d, attrs), c in zip(program, child_ns):
        o = out.setdefault(stage, {"count": 0, "total_ms": 0.0,
                                   "self_ms": 0.0, "attrs": {}})
        o["count"] += 1
        o["total_ms"] += d / 1e6
        o["self_ms"] += (d - c) / 1e6
        for k, v in attrs.items():
            o["attrs"][k] = o["attrs"].get(k, 0.0) + v
    for o in out.values():
        n = o["count"]
        o["mean_ms"] = o.pop("total_ms") / n
        o["mean_self_ms"] = o.pop("self_ms") / n
        o["attrs"] = {k: v / n for k, v in o["attrs"].items()}
    return dict(sorted(out.items(),
                       key=lambda kv: -kv[1]["count"] * kv[1]["mean_ms"]))


def render(r: dict) -> str:
    lines = [f"device busy {r['busy_ms']:.3f} ms, idle (between ops) "
             f"{r['idle_ms']:.3f} ms", "", "longest idle gaps (ms):"]
    lines += [f"  {n:<24} {ms:10.3f}" for n, ms in r["longest_gaps"]]
    lines += ["", "idle ms by stage:"]
    lines += [f"  {n:<24} {ms:10.3f}" for n, ms in
              r["idle_ms_by_stage"].items()]
    lines += ["", f"  {'stage':<16} {'count':>7} {'mean ms':>10} "
              f"{'self ms':>10}  attribute means"]
    for st, o in r["stages"].items():
        at = ", ".join(f"{k}={v:.4g}" for k, v in o["attrs"].items())
        lines.append(f"  {st:<16} {o['count']:>7} {o['mean_ms']:10.4f} "
                     f"{o['mean_self_ms']:10.4f}  {at}")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace", help="an .xplane.pb, or a directory to search")
    ap.add_argument("--top", type=int, default=10)
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)
    path = find_xplane(Path(args.trace))
    ops, bench_host = trace_reduce.read_planes(path)
    r = report(ops, bench_host, read_program_spans(path), args.top)
    print(json.dumps(r) if args.json else render(r))
    return 0


if __name__ == "__main__":
    sys.exit(main())
