"""The victim index (core/victims.py) picks the brute force's victim.

Seeded random mixes of inserts under quota and capacity pressure, lookups
with hits, store-timeout and missing-doc rollbacks, TTL evictions, the
background sweep and migrated (adopted) entries run on a ``SimClock``;
every quota or capacity victim the write path asks for is also computed
by the masked argmin over all live slots (``tests/_victim_ref.py``), on
the same state, and the two sequences must be identical, as must the
per-category live counts after every operation.
"""

import numpy as np
import pytest

from _victim_ref import VictimRecorder, assert_index_agrees, brute_counts
from repro.core import SemanticCache, SimClock
from repro.core.faults import StoreTimeout
from repro.core.hnsw import INVALID
from repro.core.policy import CategoryConfig, PolicyEngine
from repro.core.storage import Document, InMemoryStore, LatencyModelStore
from repro.obs import TraceRecorder

DIM = 16
CATS = ("a", "b", "c")


def _policies() -> PolicyEngine:
    # Quotas sum past 1, so capacity victims are picked as well.
    return PolicyEngine([
        CategoryConfig("a", threshold=0.9, ttl=3600.0, quota=0.5,
                       priority=4.0, expected_tllm_ms=500.0),
        CategoryConfig("b", threshold=0.9, ttl=2.0, quota=0.4,
                       expected_tllm_ms=200.0),
        CategoryConfig("c", threshold=0.9, ttl=3600.0, quota=0.35,
                       priority=2.0),
        CategoryConfig("r", threshold=0.9, ttl=60.0, quota=0.1,
                       allow_caching=False),
    ])


class _SlowStore(InMemoryStore):
    """Times out on a seeded tenth of the fetches."""

    def __init__(self, rng):
        super().__init__()
        self._rng = rng

    def get(self, doc_id):
        if self._rng.random() < 0.1:
            raise StoreTimeout("get")
        return super().get(doc_id)


def _unit(rng, n):
    v = rng.standard_normal((n, DIM)).astype(np.float32)
    return v / np.linalg.norm(v, axis=1, keepdims=True)


# Clock regimes: how far time moves between operations, and the modeled
# search / write-round charges. "ties" never moves (equal insertion
# times); in "sub_ms" the whole run spans under the scorer's 1 ms age
# clamp, so entries inserted at different times tie too.
REGIMES = {
    "spread": dict(step=(0.0, 0.4), search_ms=2.0, insert_ms=1.0),
    "ties": dict(step=(0.0, 0.0), search_ms=0.0, insert_ms=0.0),
    "sub_ms": dict(step=(0.0, 3e-6), search_ms=0.0, insert_ms=0.0),
}


def _drive(scorer: str, regime: str, seed: int = 7, ops: int = 260):
    cfg = REGIMES[regime]
    rng = np.random.default_rng(seed)
    clock = SimClock()
    cache = SemanticCache(_policies(), dim=DIM, capacity=96, clock=clock,
                          index_kind="flat", eviction=scorer,
                          store=_SlowStore(np.random.default_rng(seed + 1)),
                          search_ms=cfg["search_ms"],
                          insert_ms=cfg["insert_ms"])
    rec = VictimRecorder(cache)
    bank = {c: _unit(rng, 60) for c in CATS + ("r",)}
    reasons: dict[str, int] = {}
    n_req = 0
    for _ in range(ops):
        op = rng.random()
        if op < 0.45:
            k = int(rng.integers(1, 9))
            cats = [str(x) for x in rng.choice(CATS + ("r",), k,
                                               p=[.3, .3, .3, .1])]
            embs = np.stack([bank[c][rng.integers(60)] for c in cats])
            cache.insert_batch(embs, cats, [f"q{n_req + i}" for i in range(k)],
                               [f"r{n_req + i}" for i in range(k)])
            n_req += k
        elif op < 0.85:
            k = int(rng.integers(1, 9))
            cats = [str(x) for x in rng.choice(CATS, k)]
            # Repeats within a batch add several hits to one slot.
            embs = np.stack([bank[c][rng.integers(20)] for c in cats])
            for r in cache.lookup_batch(embs, cats):
                reasons[r.reason] = reasons.get(r.reason, 0) + 1
        elif op < 0.9:
            live = np.flatnonzero(cache.slot_valid)
            if live.size:    # the store loses a document: missing_doc
                cache.store.delete(int(cache.slot_doc[rng.choice(live)]))
        elif op < 0.95:
            _adopt(cache, rng, bank)
        else:
            cache.sweep_expired()
        clock.advance(float(rng.uniform(*cfg["step"])))
        assert {cid: cache._victims.count(cid) for cid in cache._cat_names
                if cache._victims.count(cid)} == brute_counts(cache)
    return cache, rec, reasons


def _adopt(cache, rng, bank) -> None:
    """Migrated entries keep their (older) insertion times and hits."""
    free = cache.capacity - cache.index._n + len(cache.index._free)
    k = min(int(rng.integers(1, 4)), free)
    if k <= 0:
        return
    cats = [str(x) for x in rng.choice(CATS, k)]
    now = cache._now()
    inserted = now - rng.uniform(0.0, 3.0, k)
    hits = rng.integers(0, 4, k)
    docs = [Document(-1, "q", "r", 0.0, c) for c in cats]
    cache.adopt_entries(np.stack([bank[c][rng.integers(60)] for c in cats]),
                        cats, inserted, hits, docs)


@pytest.mark.parametrize("regime", sorted(REGIMES))
@pytest.mark.parametrize("scorer", ["static", "cost_aware"])
def test_victims_match_brute_force(scorer, regime):
    cache, rec, reasons = _drive(scorer, regime)
    assert rec.got == rec.want
    assert len(rec.got) > 50
    assert cache.metrics.cat("a").quota_evictions > 0
    assert sum(cache.metrics.cat(c).capacity_evictions for c in CATS) > 0
    assert reasons.get("store_timeout", 0) > 0
    assert reasons.get("missing_doc", 0) > 0
    if regime == "spread":
        assert sum(cache.metrics.cat(c).ttl_evictions for c in CATS) > 0
    else:
        assert rec.ties > 0
    assert_index_agrees(cache)


def test_victims_are_scored_at_the_write_round_instant():
    """A write round's victims are all scored at its start, however long
    the store's deletes take: here the first delete moves the clock 10 s,
    which would make the young zero-hit entry the second victim."""
    clock = SimClock(100.0)
    pol = PolicyEngine([CategoryConfig("a", threshold=0.9, ttl=1e6,
                                       quota=0.5)])
    store = LatencyModelStore(InMemoryStore(), clock, get_ms=0.0,
                              put_ms=0.0, delete_ms=10_000.0)
    cache = SemanticCache(pol, dim=DIM, capacity=8, clock=clock,
                          index_kind="flat", store=store, insert_ms=0.0)
    embs = _unit(np.random.default_rng(5), 6)
    # at the round: scores 1.0, 2/2.1, 4/4.5, 2 — after 10 s the order
    # of the first three reverses
    cache.adopt_entries(embs[:4], ["a"] * 4,
                        np.array([-1.0, -2.1, -4.5, -0.5]),
                        np.array([0, 1, 3, 0]),
                        [Document(-1, f"q{i}", f"e{i}", 0.0, "a")
                         for i in range(4)])
    rec = VictimRecorder(cache)
    cache.insert_batch(embs[4:], ["a", "a"], ["q4", "q5"], ["e4", "e5"])
    kept = {cache.store.get(int(cache.slot_doc[s])).response
            for s in cache.category_slots("a")}
    assert kept == {"e0", "e3", "e4", "e5"}
    assert rec.got == rec.want and len(rec.got) == 2
    assert INVALID not in [slot for slot, _ in rec.got]


@pytest.mark.parametrize("quota_entries", [64, 1024])
def test_evict_span_counts_candidates_not_entries(quota_entries):
    """Zero-hit FIFO traffic at quota: each victim scores its bucket's head
    and one later entry (the tie check), whatever the category's size."""
    clock = SimClock()
    obs = TraceRecorder(clock)
    pol = PolicyEngine([CategoryConfig("a", threshold=0.9, ttl=1e6,
                                       quota=0.5)])
    cache = SemanticCache(pol, dim=DIM, capacity=2 * quota_entries,
                          clock=clock, index_kind="flat", obs=obs)
    rng = np.random.default_rng(3)
    embs = _unit(rng, quota_entries + 200)
    cache.insert_batch(embs[:quota_entries], ["a"] * quota_entries,
                       ["q"] * quota_entries, ["r"] * quota_entries)
    first = len(obs.spans)
    for i in range(quota_entries, quota_entries + 200):
        cache.insert(embs[i], "a", f"q{i}", f"r{i}")
    spans = [sp for sp in obs.spans[first:] if sp.stage == "evict"]
    assert len(spans) == 200
    for sp in spans:
        assert sp.attrs["victims"] == 1
        # one non-empty hit bucket, plus the constant tie check
        assert sp.attrs["scored"] <= 1 + 1
        assert sp.attrs["stale"] <= 1
    assert sum(sp.attrs["stale"] for sp in spans) >= 199
    assert cache.category_count("a") == quota_entries
    assert_index_agrees(cache)
