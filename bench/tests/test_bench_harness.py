"""The harness is driven by data: a configuration, a traffic mix and a
per-layer metric added as new files, with new entries in BENCHMARK.json,
are found by name and run without an edit to any file that exists."""

import json
import uuid

from bench import harness as H
from bench import run as R
from bench.peaks import peaks_for
from bench.tests import tiny

BASE = "flat1m-fp32.table1"


def test_per_layer_metrics_follow_their_workloads():
    spec = json.loads((H.ROOT / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        cell = H.load_cell(w["name"])
        e2e = {m["name"] for m in cell.end_to_end}
        assert {"setup_s", "served_rps", "hit_rate"} <= e2e
        for m in cell.per_layer:
            assert m["moves"] in e2e
            assert (H.ROOT / "bench" / "metrics" / f"{m['name']}.py").exists()
        assert (H.ROOT / "bench" / "drivers" /
                f"{cell.config['driver']}.py").exists()


def test_new_config_mix_and_metric_are_found_by_name(tmp_path):
    root = tiny.make_root(tmp_path)
    bench = root / "bench"
    spec = json.loads((root / "BENCHMARK.json").read_text())
    base = next(w for w in spec["workloads"] if w["name"] == BASE)
    base_cfg = next(c for c in spec["configs"] if c["name"] == base["config"])

    cfg = json.loads((root / base_cfg["file"]).read_text())
    cfg["name"] = "flat2k-fp32"
    cfg["cache"]["capacity"] = 2048
    (bench / "configs" / "flat2k-fp32.json").write_text(json.dumps(cfg))
    mix = json.loads((bench / "traffic" / f"{base['traffic']}.json")
                     .read_text())
    mix["rate_per_s"] = 150.0
    (bench / "traffic" / "table1-slow.json").write_text(json.dumps(mix))
    metric = f"zz_probe_{uuid.uuid4().hex[:8]}"
    path = H.BENCH_DIR / "metrics" / f"{metric}.py"
    path.write_text('"""Insert batches in the window."""\n\n\n'
                    'def read(ctx):\n'
                    '    return float(ctx["counters"]["insert_calls"])\n')
    try:
        spec["configs"].append(dict(base_cfg, name="flat2k-fp32",
                                    file="bench/configs/flat2k-fp32.json"))
        spec["workloads"].append(dict(base, name="flat2k-fp32.slow",
                                      config="flat2k-fp32",
                                      traffic="table1-slow"))
        spec["per_layer"].append(
            {"name": metric, "unit": "count", "better": "lower",
             "source": "program_counter", "layer": "cache front",
             "moves": "miss_p95_ms", "workloads": ["flat2k-fp32.slow"]})
        (root / "BENCHMARK.json").write_text(json.dumps(spec))
        cell = tiny.cell(root, "flat2k-fp32.slow")
        assert cell.config["cache"]["capacity"] == 2048
        assert cell.traffic["rate_per_s"] == 150.0
        line, _ = R.run_cell(cell, 3, 2.0, True, tiny.FAKE_DEVICE,
                             peaks_for("TPU v5 lite"), 0.0)
    finally:
        path.unlink()
    out = json.loads(line)
    assert out["metrics"][metric]["value"] > 0
    assert out["metrics"][metric]["unit"] == "count"
    assert out["attempted"] == 300


def test_unknown_cell_is_a_spec_error():
    import pytest
    with pytest.raises(H.SpecError):
        H.load_cell("no-such-cell")
