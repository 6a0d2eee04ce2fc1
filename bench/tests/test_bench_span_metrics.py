"""The per-layer readers of the program's spans: each on a synthetic
context, and the tiny cells' traced runs reading them."""

import json

import numpy as np
import pytest

from bench import harness as H
from bench import run as R
from bench.peaks import peaks_for
from bench.tests import tiny

SEED = 2**31 + 13


def _read(name, ctx):
    return H.metric_reader(name)(ctx)


@pytest.mark.parametrize("name,stage", [("evict_ms", "evict"),
                                        ("device_wait_ms", "device_wait"),
                                        ("delta_flush_ms", "delta_flush")])
def test_mean_span_readers(name, stage):
    ctx = {"spans": {stage: [1.0, 2.0, 6.0], "lookup": [50.0]}}
    assert _read(name, ctx) == pytest.approx(3.0)
    assert _read(name, {"spans": {"lookup": [1.0]}}) is None
    assert _read(name, {}) is None


CACHE_SPAN_METRICS = ("evict_ms", "device_wait_ms", "delta_flush_ms")


@pytest.mark.parametrize("cell,names", [
    ("flat1m-fp32.table1", CACHE_SPAN_METRICS),
    ("granite-moe-3b.table1", ())])
def test_tiny_traced_cells_read_the_new_metrics(tmp_path, cell, names):
    """The cache cell reads each new span metric; the engine cell, which
    the entries do not list, reports none of them."""
    root = tiny.make_root(tmp_path)
    line, _ = R.run_cell(tiny.cell(root, cell), SEED, 3.0, True,
                         tiny.FAKE_DEVICE, peaks_for("TPU v5 lite"), 0.0)
    got = json.loads(line)["metrics"]
    for n in names:
        assert np.isfinite(got[n]["value"]) and got[n]["value"] > 0, n
    assert not set(CACHE_SPAN_METRICS) - set(names) & set(got)
