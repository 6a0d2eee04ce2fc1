"""The engine cell end to end at a tiny size (CPU): a sound run is
correct, the fp8 control reads far above it, and each fault the cell can
have, planted in the timed path, makes it incorrect."""

import json

import jax.numpy as jnp

from bench import run as R
from bench.drivers import engine as drv
from bench.peaks import peaks_for
from bench.tests import tiny

CELL = "granite-moe-3b.table1"
SEED = 2**31 + 11


def _run(tmp_path, seconds=4.0, trace=False):
    root = tiny.make_root(tmp_path)
    return R.run_cell(tiny.cell(root, CELL), SEED, seconds, trace,
                      tiny.FAKE_DEVICE, peaks_for("TPU v5 lite"), 0.0)


def test_sound_traced_run_is_correct_and_reads_every_layer(tmp_path):
    line, checks = _run(tmp_path, trace=True)
    out = json.loads(line)
    assert out["correct"], checks
    assert checks["tokens_checked"]["value"] > 0
    for m in ("embed_ms", "generate_ms", "model_mfu"):
        assert out["metrics"][m]["value"] > 0, m
    assert 0 < out["metrics"]["model_mfu"]["value"] < 100


def test_token_altered_where_produced(tmp_path, monkeypatch):
    from repro.serving import engine as E
    init = E.ServingEngine.__init__

    def patched(self, *a, **k):
        init(self, *a, **k)
        gen, vocab = self._generate, self.model.cfg.vocab_size
        self._generate = lambda p, t: (gen(p, t) + 1) % vocab
    monkeypatch.setattr(E.ServingEngine, "__init__", patched)
    _, checks = _run(tmp_path)
    assert not checks["logit_gap_mean"]["ok"]


def test_answer_altered_where_produced(tmp_path, monkeypatch):
    real = drv.AnnotatedCache.lookup_batch

    def lookup_batch(self, emb, cats):
        res = real(self, emb, cats)
        for r in res:
            if r.hit:
                r.response = "an altered answer"
        return res
    monkeypatch.setattr(drv.AnnotatedCache, "lookup_batch", lookup_batch)
    _, checks = _run(tmp_path)
    assert not checks["hit_response_mismatches"]["ok"]


def test_write_back_leaves_state_unchanged(tmp_path, monkeypatch):
    real = drv.AnnotatedCache.insert_batch
    warm = len(drv._buckets(8))            # the warm-up steps' writes
    state = {"calls": 0}

    def insert_batch(self, emb, cats, req, resp):
        state["calls"] += 1
        if state["calls"] <= warm:
            return real(self, emb, cats, req, resp)
        return list(range(len(cats)))
    monkeypatch.setattr(drv.AnnotatedCache, "insert_batch", insert_batch)
    _, checks = _run(tmp_path)
    assert not checks["device_row_mismatches"]["ok"]


def test_fp8_control_reads_far_above_the_program(tmp_path):
    """The reference in the program's place with fp8 operands, judged by
    the same checks and limits, comes out not correct."""
    from bench import control
    root = tiny.make_root(tmp_path)
    r = control.readings(tiny.cell(root, CELL), [SEED], 4.0)[0]
    prog, ctl = r["program"], r["control"]
    assert ctl["logit_gap_max"] > 3 * prog["logit_gap_max"]
    assert ctl["logit_gap_mean"] > 3 * prog["logit_gap_mean"]
    assert jnp.isfinite(prog["logit_gap_max"])
    assert r["correct"] is True
    assert r["control_correct"] is False
