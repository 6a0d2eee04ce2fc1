"""Tiny cells for the CPU tests: the same drivers, traffic generator and
checks as the chip cells, at sizes a test run holds (interpret-mode
kernels)."""

from __future__ import annotations

import copy
import json
from pathlib import Path

from bench import harness as H

REAL = H.BENCH_DIR
TINY_ARCH = {"name": "tiny-moe", "family": "moe", "n_layers": 2,
             "d_model": 128, "n_heads": 4, "n_kv_heads": 2, "head_dim": 32,
             "d_ff": 64, "d_ff_expert": 64, "n_experts": 8, "moe_top_k": 2,
             "moe_every": 1, "vocab_size": 512, "rope_theta": 10000.0,
             "capacity_factor": 1.5, "remat": "none", "norm_eps": 1e-6,
             "dtype": "bfloat16"}
FAKE_DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}


def make_root(tmp: Path, rate: float = 200.0, capacity: int = 4096,
              pool_scale: float = 0.05) -> Path:
    """A checkout-shaped directory whose BENCHMARK.json holds the two cells
    at tiny sizes (their configuration and traffic files beside it); the
    code that runs them is the real benchmark's."""
    tmp = Path(tmp)
    bench = tmp / "bench"
    (bench / "configs").mkdir(parents=True)
    (bench / "traffic").mkdir()
    spec = json.loads((H.ROOT / "BENCHMARK.json").read_text())
    spec = copy.deepcopy(spec)
    for c in spec["configs"]:
        cfg = json.loads((H.ROOT / c["file"]).read_text())
        cfg["cache"]["capacity"] = capacity
        if "arch" in cfg:
            cfg["arch"] = dict(TINY_ARCH)
            cfg["engine"] = {"max_batch": 8, "prompt_len": 32,
                             "max_new_tokens": 4}
        (bench / "configs" / Path(c["file"]).name).write_text(
            json.dumps(cfg))
    for w in spec["workloads"]:
        tr = json.loads((REAL / "traffic" / f"{w['traffic']}.json")
                        .read_text())
        for c in tr["categories"]:
            c["pool"] = max(16, int(c["pool"] * pool_scale))
        tr["rate_per_s"] = rate
        if "history_requests" in tr:
            tr["history_requests"] = min(tr["history_requests"], 4000)
        if "history_entries" in tr:
            tr["history_entries"] = 300
            tr["prompt"] = {"min": 4, "max": 32, "pareto_alpha": 1.2}
            tr["rate_per_s"] = rate / 20
            tr["check_requests"] = 2
        tr["check_lookups"] = min(tr.get("check_lookups", 0), 128)
        (bench / "traffic" / f"{w['traffic']}.json").write_text(
            json.dumps(tr))
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp


def cell(root: Path, name: str) -> H.Cell:
    return H.load_cell(name, root=root, bench_dir=Path(root) / "bench")
