"""The engine cell at a tiny size (CPU) under the engine's split step: a
lookup that finds hits and misses returns its hits one step before its
misses are generated. The cell's reference accounting (each request
judged against the entries born before its step) holds, and planted
faults still make the run incorrect while the split is engaged."""

import json

import numpy as np
import pytest

from bench import run as R
from bench.drivers import engine as drv
from bench.peaks import peaks_for
from bench.tests import tiny

CELL = "granite-moe-3b.table1"
SEED = 2**31 + 11
RATE = 2000.0       # the engine cell offers a twentieth: 100 req/s, so
                    # steps hold several requests and mix hits and misses


def _run(tmp_path, monkeypatch, fault=None):
    """One 4 s run of the tiny cell at RATE; returns (correct, checks, log): the
    log holds each step's responses with the number of lookups made by
    its return, the window's first request id, its record, and the
    engine's stats."""
    from repro.serving import engine as E
    log = {"lookups": 0, "steps": []}
    lookup, step = drv.AnnotatedCache.lookup_batch, E.ServingEngine.step
    window = drv.Session.serve_window

    def counted_lookup(self, emb, cats):
        log["lookups"] += 1
        res = lookup(self, emb, cats)
        if fault == "answer":
            for r in res:
                if r.hit:
                    r.response = "an altered answer"
        return res

    def logged_step(self):
        out = step(self)
        log["steps"].append((log["lookups"],
                             [(r.req_id, r.cached) for r in out]))
        return out

    def logged_window(self, *a, **k):
        log["rid0"] = self.engine._next_id
        w, layer, rec = window(self, *a, **k)
        log["rec"], log["stats"] = rec, self.engine.stats
        return w, layer, rec

    monkeypatch.setattr(drv.AnnotatedCache, "lookup_batch", counted_lookup)
    monkeypatch.setattr(E.ServingEngine, "step", logged_step)
    monkeypatch.setattr(drv.Session, "serve_window", logged_window)
    if fault == "write_back":
        insert, warm = drv.AnnotatedCache.insert_batch, len(drv._buckets(8))
        calls = [0]

        def unchanged(self, emb, cats, req, resp):
            calls[0] += 1
            if calls[0] <= warm:
                return insert(self, emb, cats, req, resp)
            return list(range(len(cats)))
        monkeypatch.setattr(drv.AnnotatedCache, "insert_batch", unchanged)
    if fault == "swapped_embeddings":
        gen = E.ServingEngine._generate_misses

        def swapped(self, misses):
            embs = [r.emb for r in misses]
            for r, e in zip(misses, embs[1:] + embs[:1]):
                r.emb = e
            return gen(self, misses)
        monkeypatch.setattr(E.ServingEngine, "_generate_misses", swapped)
    root = tiny.make_root(tmp_path, rate=RATE)
    line, checks = R.run_cell(tiny.cell(root, CELL), SEED, 4.0, False,
                              tiny.FAKE_DEVICE, peaks_for("TPU v5 lite"), 0.0)
    return json.loads(line)["correct"], checks, log


def _by_lookup(log) -> dict[int, list[tuple[int, bool]]]:
    """The window's requests (index, hit) by the lookup that decided
    them: a deferred generate makes no lookup, so its misses share their
    hits' lookup count."""
    out: dict[int, list[tuple[int, bool]]] = {}
    for k, served in log["steps"]:
        for rid, hit in served:
            if rid >= log["rid0"]:
                out.setdefault(k, []).append((rid - log["rid0"], hit))
    return out


def test_hits_carry_an_earlier_step_than_their_lookups_misses(
        tmp_path, monkeypatch):
    correct, checks, log = _run(tmp_path, monkeypatch)
    assert correct, checks
    step_of = log["rec"]["step_of"]
    assert (step_of >= 0).all()
    mixed = early = 0
    for reqs in _by_lookup(log).values():
        hits = [g for g, hit in reqs if hit]
        misses = [g for g, hit in reqs if not hit]
        if hits and misses:
            mixed += 1
            early += len(hits)
            assert np.max(step_of[hits]) < np.min(step_of[misses])
    st = log["stats"]
    assert mixed > 0 and st.deferred_generates == mixed
    assert st.hits_before_generate == early


@pytest.mark.parametrize("fault",
                         ["answer", "write_back", "swapped_embeddings"])
def test_planted_faults_are_caught_under_the_split(tmp_path, monkeypatch,
                                                   fault):
    correct, checks, log = _run(tmp_path, monkeypatch, fault)
    assert log["stats"].deferred_generates > 0
    assert not correct
    failed = {k for k, c in checks.items() if not c.get("ok", True)}
    assert failed & {"hit_response_mismatches", "decision_mismatches",
                     "device_row_mismatches"}, checks
