"""Find a cell's knee: the highest offered rate it sustains.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> --rates 500,1000,2000

One process; for each rate, in order, a fresh set-up (the state the cell
starts from) and one open-loop window at that rate. Each prints a JSON
line: the offered rate, requests answered per second inside the window,
hit and miss p95, and the backlog left at the window's end. Past the
knee the backlog grows with the window and the tails with it. The cell's
traffic file then fixes its rate at about four fifths of the knee; the
benchmark itself never searches for a rate.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness as H  # noqa: E402


def sweep(cell: H.Cell, seed: int, seconds: float, rates: list[float]):
    """One line per rate, each window run from a fresh set-up."""
    drv = H.driver_for(cell)
    for rate in rates:
        session = drv.prepare(cell, seed)
        w = session.serve_window(rate, seconds)[0]
        del session
        gc.collect()
        e2e = H.end_to_end(w, 0.0)
        yield {"rate": rate, "served_rps": e2e["served_rps"],
               "hit_p95_ms": e2e["hit_p95_ms"],
               "miss_p95_ms": e2e["miss_p95_ms"],
               "hit_rate": e2e["hit_rate"],
               "backlog_at_end": int(np.sum(~w.answered)),
               **H.lateness_line(w)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    cell = H.load_cell(args.workload)
    try:
        print(json.dumps(H.device_info(int(cell.entry["chips"]))),
              flush=True)
    except H.NoAccelerator as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 3
    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    for line in sweep(cell, args.seed, args.seconds,
                      [float(r) for r in args.rates.split(",")]):
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
