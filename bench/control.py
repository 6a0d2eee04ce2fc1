"""Readings that set the limits of ``correct``: the program's, and its
control's, on several seeds in one process.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 10

For each seed the cell is set up and runs a window at its own load, as in
``run.py``; then the window's answers go through the cell's checks twice:
as the program produced them, and with the control in the program's
place (the reference, one precision step below the configuration's).
Both are judged by the same limits, and the control has to come out not
correct. One JSON line per seed: each side's ``correct`` and the numbers
compared. The benchmark's own runs never run the control.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness as H  # noqa: E402


def readings(cell: H.Cell, seeds: list[int], seconds: float) -> list[dict]:
    drv = H.driver_for(cell)
    out = []
    for seed in seeds:
        t = time.monotonic()
        checks, control = drv.readings(cell, seed, seconds)
        out.append({"seed": seed, "seconds": time.monotonic() - t,
                    "correct": H.is_correct(checks),
                    "control_correct": H.is_correct(control),
                    "program": {k: v["value"] for k, v in checks.items()},
                    "control": {k: v["value"] for k, v in control.items()
                                if v is not checks.get(k)}})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = H.load_cell(args.workload)
    try:
        print(json.dumps(H.device_info(int(cell.entry["chips"]))),
              flush=True)
    except H.NoAccelerator as e:
        print(f"control: {e}", file=sys.stderr)
        return 3
    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    for r in readings(cell, [int(s) for s in args.seeds.split(",")],
                      args.seconds):
        print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
