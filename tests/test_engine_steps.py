"""The engine's step scheduler: a lookup that finds hits and misses hands
its hits back at once and leaves its misses, looked up, at the head of
the queue; the next step generates and writes them back with no lookup
of its own. A stub generate (records its calls, sleeps) stands in for the
model, so these run in a second."""

import time

import numpy as np
import pytest

from repro.core import SemanticCache, SimClock
from repro.core.policy import PolicyEngine, paper_policies
from repro.distributed.fault import StepWatchdog
from repro.obs import TraceRecorder
from repro.obs.trace import check_span_accounting

CAT = "code_generation"
PROMPT = np.arange(2, 10)


def _text(k: int) -> str:
    """A question far from every other ``_text`` under the feature hash."""
    rng = np.random.default_rng(1000 + k)
    return " ".join("".join(rng.choice(list("abcdefghijklmnop"), 7))
                    for _ in range(8))


class _Stub:
    """A generate that records the rows of each call and sleeps."""

    def __init__(self, sleep_s: float = 0.0):
        self.sleep_s = sleep_s
        self.calls: list[int] = []

    def __call__(self, params, toks):
        self.calls.append(int(toks.shape[0]))
        time.sleep(self.sleep_s)
        return np.zeros((toks.shape[0], 4), np.int32)


def _engine(max_batch=4, sleep_s=0.0, obs=None, clock=None, watchdog=None):
    """An engine on a tiny model whose generate is a ``_Stub``, with a
    count of its cache's lookups in ``eng.lookups``."""
    from repro.configs import get_config
    from repro.models import Model
    from repro.serving.engine import ServingEngine
    cfg = get_config("llama3_2_3b").reduced(n_layers=2, d_model=64,
                                            vocab_size=256)
    cache = SemanticCache(PolicyEngine(paper_policies()), capacity=256,
                          clock=clock or SimClock(), index_kind="flat",
                          obs=obs)
    eng = ServingEngine(Model(cfg), None, cache, max_batch=max_batch,
                        prompt_len=16, max_new_tokens=4, obs=obs,
                        watchdog=watchdog)
    eng._generate = _Stub(sleep_s)
    eng.lookups = []
    real = cache.lookup_batch

    def counted(embs, cats):
        eng.lookups.append(len(cats))
        return real(embs, cats)
    cache.lookup_batch = counted
    return eng


def _cache_texts(eng, ks) -> None:
    """Serve ``_text(k)`` for each k as misses, so later copies hit."""
    for k in ks:
        eng.submit(_text(k), CAT, PROMPT)
    eng.drain()


def _submit(eng, ks) -> list[int]:
    return [eng.submit(_text(k), CAT, PROMPT) for k in ks]


def test_mixed_step_answers_hits_before_its_misses_are_generated():
    eng = _engine(max_batch=4, sleep_s=0.2)
    _cache_texts(eng, [0, 1])
    gen, cached = eng._generate, len(eng.cache)
    gen.calls.clear()
    lookups = len(eng.lookups)
    t0 = time.monotonic()
    rids = _submit(eng, [0, 10, 1, 11])      # hit, miss, hit, miss
    hits = eng.step()
    hits_ms = (time.monotonic() - t0) * 1e3
    assert [r.req_id for r in hits] == [rids[0], rids[2]]
    assert all(r.cached for r in hits) and gen.calls == []
    assert all(r.latency_ms <= hits_ms < 200 for r in hits)
    assert [r.req_id for r in eng.queue] == [rids[1], rids[3]]
    assert all(r.emb is not None for r in eng.queue)
    assert len(eng.lookups) == lookups + 1

    misses = eng.step()
    assert [r.req_id for r in misses] == [rids[1], rids[3]]
    assert not any(r.cached for r in misses)
    assert all(r.latency_ms >= 200 for r in misses)
    assert gen.calls == [8] and len(eng.lookups) == lookups + 1
    assert eng.queue == [] and len(eng.cache) == cached + 2
    # written back: the same questions now hit, in one step
    _submit(eng, [10, 11])
    assert all(r.cached for r in eng.step()) and eng.queue == []


def test_deferred_misses_come_before_later_arrivals():
    """Requests submitted after a mixed lookup wait behind its misses:
    the next step generates the misses alone, the one after looks the
    newcomers up."""
    eng = _engine(max_batch=2)
    _cache_texts(eng, [0])
    rids = _submit(eng, [0, 10])
    assert [r.req_id for r in eng.step()] == [rids[0]]
    late = _submit(eng, [20, 21])
    assert [r.req_id for r in eng.step()] == [rids[1]]
    assert [r.req_id for r in eng.queue] == late
    assert [r.req_id for r in eng.step()] == late


@pytest.mark.parametrize("kind", ["all_miss", "all_hit"])
def test_unmixed_lookup_finishes_in_one_step(kind):
    eng = _engine(max_batch=4)
    _cache_texts(eng, [0, 1, 2])
    eng._generate.calls.clear()
    ks = [10, 11, 12] if kind == "all_miss" else [0, 1, 2]
    rids = _submit(eng, ks)
    out = eng.step()
    assert sorted(r.req_id for r in out) == rids and eng.queue == []
    assert all(r.cached == (kind == "all_hit") for r in out)
    assert len(eng._generate.calls) == (kind == "all_miss")
    assert eng.stats.deferred_generates == 0
    assert eng.stats.hits_before_generate == 0


def test_engine_stats_count_early_hits_and_deferred_generates():
    eng = _engine(max_batch=4)
    _cache_texts(eng, [0, 1, 2])
    served = []
    for ks in ([0, 10, 1, 11],               # mixed: 2 hits early
               [2, 12, 13, 14],              # mixed: 1 hit early
               [0, 1],                       # all hits: not early
               [15]):                        # all miss: not deferred
        _submit(eng, ks)
        served += eng.drain()
    st = eng.stats
    assert len(served) == 11 and st.served == 3 + 11
    assert st.hits_before_generate == 3
    assert st.deferred_generates == 2
    assert st.cache_hits == 5 and st.reasons["model"] == 9


def test_queue_wait_once_per_request_and_step_phases():
    """Each request's queue wait is recorded once, at its lookup; the
    ``engine_step`` span names its phase, and the deferred generate's
    ``model_generate`` nests under a ``generate`` step."""
    clock = SimClock()
    rec = TraceRecorder(clock)
    eng = _engine(max_batch=4, obs=rec, clock=clock)
    _cache_texts(eng, [0, 1])
    served = []
    for ks in ([0, 10, 1, 11], [0, 1], [12, 13]):   # mixed, hits, misses
        _submit(eng, ks)
        served += eng.drain()
    waits = [sp for sp in rec.spans if sp.stage == "queue_wait"]
    assert len(waits) == eng.stats.served == 2 + len(served)
    steps = [sp for sp in rec.spans if sp.stage == "engine_step"]
    assert [sp.attrs["phase"] for sp in steps] == [
        "lookup+generate", "lookup", "generate", "lookup",
        "lookup+generate"]
    by_id = {sp.span_id: sp for sp in rec.spans}
    phases = [by_id[sp.parent_id].attrs["phase"] for sp in rec.spans
              if sp.stage == "model_generate"]
    assert phases == ["lookup+generate", "generate", "lookup+generate"]
    assert check_span_accounting(rec) == []


def test_watchdog_times_a_lookup_and_its_deferred_generate_as_one_step():
    """Mixed lookups alternate with their deferred generates and with
    all-hit lookups: the watchdog sees one step per lookup, so no
    generate is flagged against a median of lookup halves; a deferred
    generate slowed far past the factor is flagged once."""
    wd = StepWatchdog(timeout_factor=5.0, min_history=5)
    eng = _engine(max_batch=2, sleep_s=0.1, watchdog=wd)
    _cache_texts(eng, [0])
    for k in range(8):
        _submit(eng, [0, 100 + k])
        assert [r.cached for r in eng.step()] == [True]
        assert [r.cached for r in eng.step()] == [False]
        _submit(eng, [0])
        assert [r.cached for r in eng.step()] == [True]
    assert eng.stats.straggler_steps == 0
    assert eng.stats.deferred_generates == 8
    eng._generate.sleep_s = 1.5
    _submit(eng, [0, 200])
    eng.step()
    eng.step()
    assert eng.stats.straggler_steps == wd.straggler_events == 1
