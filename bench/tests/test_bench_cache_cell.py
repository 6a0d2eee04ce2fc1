"""The cache-tier cell end to end at a tiny size (CPU): a sound run is
correct, its control's reading departs from the program's, and each
fault the cell can have, planted in the timed path, makes it incorrect."""

import numpy as np
import pytest

from bench import run as R
from bench.drivers import cache as drv
from bench.peaks import peaks_for
from bench.tests import tiny

CELL = "flat1m-fp32.table1"
SEED = 2**31 + 5


def _run(tmp_path, seconds=2.0, trace=False):
    root = tiny.make_root(tmp_path)
    line, checks = R.run_cell(tiny.cell(root, CELL), SEED, seconds, trace,
                              tiny.FAKE_DEVICE, peaks_for("TPU v5 lite"),
                              0.0)
    return line, checks


def test_sound_run_is_correct_and_reports_its_metrics(tmp_path):
    """Every check passes, except that the score-precision numbers are
    held to the chip's limits, which the CPU's interpret-mode scan (a
    different accumulation order) may exceed; here they only have to be
    at fp32 rounding."""
    import json
    line, checks = _run(tmp_path)
    out = json.loads(line)
    precision = {"score_err_max", "score_err_rms"}
    bad = {k for k, c in checks.items() if not c.get("ok", True)}
    assert not bad - precision, checks
    assert checks["score_err_max"]["value"] < 2e-6
    assert checks["score_err_rms"]["value"] < 5e-7
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {"hit_p95_ms", "miss_p95_ms",
                                   "served_rps", "hit_rate", "setup_s"}
    assert checks["decisions_checked"]["value"] > 0
    assert checks["hits_checked"]["value"] > 0
    assert checks["evictions_checked"]["value"] > 0


def _after_setup(monkeypatch, plant):
    """Plant a fault once set-up is done: the window runs on it."""
    real = drv.setup

    def setup(st):
        real(st)
        plant(st)
    monkeypatch.setattr(drv, "setup", setup)


def test_answer_altered_where_produced(tmp_path, monkeypatch):
    def plant(st):
        real = st.cache.lookup_batch

        def lookup_batch(emb, cats):
            res = real(emb, cats)
            for r in res:
                if r.hit:
                    r.response = "an altered answer"
                    break
            return res
        st.cache.lookup_batch = lookup_batch
    _after_setup(monkeypatch, plant)
    _, checks = _run(tmp_path)
    assert not checks["hit_response_mismatches"]["ok"]


def test_write_back_leaves_state_unchanged(tmp_path, monkeypatch):
    def plant(st):
        top = [st.capacity - 1]

        def insert_batch(emb, cats, req, resp):
            out = list(range(top[0] - len(cats) + 1, top[0] + 1))
            top[0] -= len(cats)
            return out
        st.cache.insert_batch = insert_batch
    _after_setup(monkeypatch, plant)
    _, checks = _run(tmp_path, seconds=3.0)
    assert not checks["device_row_mismatches"]["ok"]


def test_half_of_the_batch_left_out(tmp_path, monkeypatch):
    from repro.core.cache import CacheResult

    def plant(st):
        real = st.cache.lookup_batch

        def lookup_batch(emb, cats):
            h = (len(cats) + 1) // 2
            return real(emb[:h], cats[:h]) + [
                CacheResult(False, category=c, reason="no_match")
                for c in cats[h:]]
        st.cache.lookup_batch = lookup_batch
    _after_setup(monkeypatch, plant)
    _, checks = _run(tmp_path, seconds=3.0)
    assert not checks["decision_mismatches"]["ok"]


def test_delta_flush_left_out(tmp_path, monkeypatch):
    def plant(st):
        from repro.kernels import ops
        monkeypatch.setattr(ops, "scatter_rows",
                            lambda table, rows, vals: table)
    _after_setup(monkeypatch, plant)
    _, checks = _run(tmp_path, seconds=3.0)
    assert not checks["device_row_mismatches"]["ok"]


def test_control_reads_above_the_program(tmp_path):
    """The reference in the program's place at three bf16 passes, judged
    by the same checks and limits: its score error against the exact
    reference exceeds the program's, and it comes out not correct."""
    from bench import control
    root = tiny.make_root(tmp_path)
    cell = tiny.cell(root, CELL)
    r = control.readings(cell, [SEED], 2.0)[0]
    lim = cell.config["limits"]
    assert r["control"]["score_err_max"] > r["program"]["score_err_max"]
    assert r["control"]["score_err_rms"] > lim["score_err_rms"]
    assert np.isfinite(r["control"]["score_err_max"])
    assert r["control_correct"] is False


def test_eviction_of_a_wrong_victim(tmp_path, monkeypatch):
    """The quota victim chosen by the inverse of the cache's score (the
    newest, most-hit entry goes first) fails the eviction replay."""
    def plant(st):
        ev = st.cache._evictor
        real = ev.score
        monkeypatch.setattr(ev, "score",
                            lambda cache, slots: -real(cache, slots))
    _after_setup(monkeypatch, plant)
    _, checks = _run(tmp_path, seconds=3.0)
    assert checks["evictions_checked"]["value"] > 0
    assert not checks["eviction_mismatches"]["ok"]


@pytest.mark.parametrize("seed", [0, 2**32 + 3])
def test_command_exits_nonzero_without_a_tpu(seed):
    """On the CPU the command prints no result and exits non-zero."""
    import os
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(R.ROOT / "bench" / "run.py"),
                        "--workload", CELL, "--seed", str(seed),
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, timeout=120)
    assert p.returncode != 0
    assert "correct" not in p.stdout
    assert "not a TPU" in p.stderr
