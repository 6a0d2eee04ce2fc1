"""Sharded cache tier (core/shard.py): planner placement, sharded-vs-
single parity, live category migration.

The parity tests are the subsystem's contract: because search is
category-masked and quota ceilings resolve against the GLOBAL capacity
on every shard, a ``ShardedSemanticCache`` over any shard count must
return bit-identical {hit, expired, miss} classes and serve the same
documents as one ``SemanticCache`` on the same workload — across index
kinds, resident dtypes and the host/device search paths. Everything is
seeded and clocked on ``SimClock``, so the runs are exactly
reproducible.
"""

import numpy as np
import pytest

from _victim_ref import VictimRecorder, assert_index_agrees
from repro.core import SemanticCache, SimClock
from repro.core.economics import ResidencyModel
from repro.core.hnsw import INVALID, quantize_rows
from repro.core.policy import CategoryConfig, PolicyEngine, paper_policies
from repro.core.shard import (CRC32Planner, CategoryMigration, ShardPlanner,
                              ShardedSemanticCache, crc32_shard)

DIM = 48


def _policies() -> PolicyEngine:
    return PolicyEngine([
        CategoryConfig("a", threshold=0.80, ttl=25.0, quota=0.30,
                       priority=2.0),
        CategoryConfig("b", threshold=0.78, ttl=1e6, quota=0.30),
        CategoryConfig("c", threshold=0.75, ttl=1e6, quota=0.05,
                       priority=0.5),
        CategoryConfig("d", threshold=0.95, ttl=1.0, quota=0.0,
                       allow_caching=False),
    ])


def _banks(n_intents: int = 64) -> dict[str, np.ndarray]:
    """Deterministic per-category intent vectors (unit rows; at dim 48
    cross-intent cosines sit ~0.14 ± 0.14, far below every τ)."""
    banks = {}
    for k, cat in enumerate(("a", "b", "c", "d")):
        rng = np.random.default_rng(100 + k)
        v = rng.standard_normal((n_intents, DIM)).astype(np.float32)
        banks[cat] = v / np.linalg.norm(v, axis=1, keepdims=True)
    return banks


def _workload(rounds: int = 8) -> list[list[tuple[str, int]]]:
    """Per-round (category, intent) schedule: revisits (hits), fresh
    intents (misses → inserts), category "c" overflowing its 12-entry
    quota, and a compliance-blocked "d" query per round."""
    sched = []
    seen = {"a": 0, "b": 0, "c": 0}
    for r in range(rounds):
        batch: list[tuple[str, int]] = []
        for cat, new in (("a", 2), ("b", 2), ("c", 3)):
            for j in range(3):      # revisit earlier intents (if any)
                if seen[cat]:
                    batch.append((cat, (r + j) % seen[cat]))
            for j in range(new):    # fresh traffic
                batch.append((cat, seen[cat] + j))
            seen[cat] += new
        batch.append(("d", r))
        sched.append(batch)
    return sched


def _run(cache, banks, sched) -> list[tuple]:
    """Drive one cache through the schedule; returns the observable
    trace: (hit, reason-class, response) per query per round."""
    trace = []
    for r, batch in enumerate(sched):
        embs = np.stack([banks[c][i] for c, i in batch])
        cats = [c for c, _ in batch]
        results = cache.lookup_batch(embs, cats)
        for (c, i), res in zip(batch, results):
            trace.append((res.hit, res.reason, res.response))
        miss = [k for k, res in enumerate(results)
                if not res.hit and res.reason != "compliance"]
        if miss:
            cache.insert_batch(
                embs[miss], [cats[k] for k in miss],
                [f"q:{batch[k][0]}:{batch[k][1]}" for k in miss],
                [f"r:{batch[k][0]}:{batch[k][1]}" for k in miss])
        cache.clock.advance(10.0)
        if r % 3 == 2:
            cache.sweep_expired()
    return trace


@pytest.mark.parametrize("index_kind,emb_dtype,use_device", [
    ("flat", "float32", False),
    ("flat", "float32", True),
    ("flat", "int8", True),
    ("hnsw", "float32", False),
    ("hnsw", "float32", True),
    ("hnsw", "int8", True),
])
def test_sharded_matches_single_cache(index_kind, emb_dtype, use_device):
    """Property: over shard counts {1, 2, 4}, both index kinds and both
    resident dtypes, the sharded cache's hit/expired/miss classes and
    served documents are bit-identical to a single cache's on the same
    mixed-category workload (with TTL expiry, quota evictions and
    compliance rejects all exercised)."""
    banks = _banks()
    sched = _workload()
    kw = dict(dim=DIM, capacity=256, index_kind=index_kind,
              use_device=use_device, emb_dtype=emb_dtype, seed=0)
    baseline = _run(SemanticCache(_policies(), clock=SimClock(), **kw),
                    banks, sched)
    assert any(t[1] == "expired" for t in baseline)
    assert any(t[1] == "hit" for t in baseline)
    assert any(t[1] == "compliance" for t in baseline)
    for n in (1, 2, 4):
        sharded = ShardedSemanticCache(_policies(), n_shards=n,
                                       clock=SimClock(), **kw)
        trace = _run(sharded, banks, sched)
        assert trace == baseline, \
            f"n_shards={n} diverged from the single cache"
        if n > 1:   # the planner actually spread the categories
            homes = {sharded.shard_of(c) for c in ("a", "b", "c")}
            assert len(homes) > 1


def test_sharded_quota_ceiling_matches_global_capacity():
    """Quota math resolves against the GLOBAL capacity on every shard:
    category "c" (quota 0.05 → 12 of 256) caps at the same entry count
    under 1 and 4 shards."""
    banks = _banks()
    sched = _workload()
    counts = []
    for n in (1, 4):
        cache = ShardedSemanticCache(_policies(), dim=DIM, capacity=256,
                                     n_shards=n, clock=SimClock(),
                                     index_kind="flat")
        _run(cache, banks, sched)
        counts.append(cache.category_count("c"))
    assert counts[0] == counts[1] == 12


def test_global_slot_encoding_and_doc_ids():
    """Returned slots are globally encoded (shard · shard_capacity +
    local), doc ids are globally unique across shards, and doc_id_of
    decodes both."""
    cache = ShardedSemanticCache(_policies(), dim=DIM, capacity=64,
                                 n_shards=2, clock=SimClock(),
                                 index_kind="flat")
    banks = _banks()
    slots = cache.insert_batch(
        np.stack([banks["a"][0], banks["b"][0]]), ["a", "b"],
        ["qa", "qb"], ["ra", "rb"])
    shards = {cache.shard_of_slot(s)[0] for s in slots}
    assert shards == {0, 1}
    doc_ids = [cache.doc_id_of(s) for s in slots]
    assert len(set(doc_ids)) == 2
    assert {d % 2 for d in doc_ids} == {0, 1}   # strided id sequences
    res = cache.lookup_batch(np.stack([banks["a"][0], banks["b"][0]]),
                             ["a", "b"])
    assert [r.slot for r in res] == slots
    assert [r.doc_id for r in res] == doc_ids


def test_aggregated_stats_views():
    """sync_stats / last_lookup_stats / metrics merge across shards."""
    cache = ShardedSemanticCache(_policies(), dim=DIM, capacity=128,
                                 n_shards=2, clock=SimClock(),
                                 index_kind="flat", use_device=True)
    banks = _banks()
    embs = np.stack([banks["a"][0], banks["b"][0], banks["a"][1]])
    cats = ["a", "b", "a"]
    cache.insert_batch(embs, cats, ["q0", "q1", "q2"], ["r0", "r1", "r2"])
    res = cache.lookup_batch(embs, cats)
    assert all(r.hit for r in res)
    sync = cache.sync_stats
    assert len(sync["per_shard"]) == 2
    assert sync["bytes_synced"] == sum(s["bytes_synced"]
                                       for s in sync["per_shard"])
    assert sync["full_uploads"] >= 2            # one initial upload per shard
    ls = cache.last_lookup_stats
    assert ls["batch"] == 3
    assert set(ls["per_shard"]) == {0, 1}
    snap = cache.metrics.snapshot()
    assert snap["a"]["lookups"] == 2 and snap["b"]["lookups"] == 1
    assert cache.metrics.overall_hit_rate() == 1.0
    rep = cache.shard_report()
    assert sum(r["entries"] for r in rep) == len(cache) == 3
    assert all(r["resident_bytes"] > 0 for r in rep)


def _admission_policies() -> PolicyEngine:
    """_policies() with admission gating on "a"/"c" and distinct miss
    costs, so admit-on-2nd-touch skips and cost_aware scoring both fire."""
    return PolicyEngine([
        CategoryConfig("a", threshold=0.80, ttl=25.0, quota=0.30,
                       priority=2.0, admit_after=2, expected_tllm_ms=800.0),
        CategoryConfig("b", threshold=0.78, ttl=1e6, quota=0.30,
                       expected_tllm_ms=200.0),
        CategoryConfig("c", threshold=0.75, ttl=1e6, quota=0.05,
                       priority=0.5, admit_after=2, expected_tllm_ms=500.0),
        CategoryConfig("d", threshold=0.95, ttl=1.0, quota=0.0,
                       allow_caching=False),
    ])


@pytest.mark.parametrize("index_kind,use_device", [
    ("flat", False),
    ("flat", True),
    ("hnsw", True),
])
def test_sharded_parity_with_admission_and_cost_aware_eviction(
        index_kind, use_device):
    """The parity contract survives the new control plane: with
    admit_after=2 on two categories AND cost_aware eviction scoring, the
    sharded cache still reproduces the single cache bit-for-bit over
    shard counts {1, 2, 4} — admission state is seeded from the category
    NAME (not the shard's seed+i), and both quota eviction and admission
    skips are shard-local decisions over identical per-category streams.
    """
    banks = _banks()
    sched = _workload(rounds=10)
    kw = dict(dim=DIM, capacity=256, index_kind=index_kind,
              use_device=use_device, eviction="cost_aware", seed=0)
    single = SemanticCache(_admission_policies(), clock=SimClock(), **kw)
    baseline = _run(single, banks, sched)
    snap = single.metrics.snapshot()
    base_skips = {c: s["admission_skips"] for c, s in snap.items()}
    assert base_skips["a"] > 0 and base_skips["c"] > 0   # the gate fired
    assert base_skips["b"] == 0                          # ungated category
    # gated intents that DO repeat still get admitted and then hit
    assert any(t[1] == "hit" for t in baseline)
    assert single.eviction == "cost_aware"
    for n in (1, 2, 4):
        sharded = ShardedSemanticCache(_admission_policies(), n_shards=n,
                                       clock=SimClock(), **kw)
        trace = _run(sharded, banks, sched)
        assert trace == baseline, \
            f"n_shards={n} diverged with admission + cost_aware enabled"
        ssnap = sharded.metrics.snapshot()
        assert {c: s["admission_skips"] for c, s in ssnap.items()} \
            == base_skips
        agg = sharded.last_insert_stats
        assert agg["admission_skips"] == sum(
            s.get("admission_skips", 0) for s in agg["per_shard"].values())


def test_migration_hands_admission_state_to_target():
    """After a live migration, the target shard continues the source's
    repetition counts: an intent one touch short of admission on the
    source is admitted by its FIRST post-cutover touch on the target."""
    pol = PolicyEngine([
        CategoryConfig("a", threshold=0.80, ttl=1e6, quota=0.45,
                       admit_after=3),
        CategoryConfig("b", threshold=0.80, ttl=1e6, quota=0.45),
    ])
    planner = ShardPlanner(2, 256, policies=pol)
    planner.plan({"a": 0.45, "b": 0.45})
    cache = ShardedSemanticCache(pol, dim=DIM, capacity=256, n_shards=2,
                                 clock=SimClock(), index_kind="flat",
                                 planner=planner)
    banks = _banks()
    emb = banks["a"][:1]
    for _ in range(2):                      # two touches: still below k=3
        cache.insert_batch(emb, ["a"], ["q"], ["r"])
    assert cache.category_count("a") == 0
    src, dst = cache.shard_of("a"), 1 - cache.shard_of("a")
    assert cache.shards[src].admission.stats()["a"]["observations"] == 2
    cache.migrate_category("a", dst)
    assert cache.shard_of("a") == dst
    assert "a" not in cache.shards[src].admission.stats()   # detached
    assert cache.shards[dst].admission.stats()["a"]["observations"] == 2
    cache.insert_batch(emb, ["a"], ["q"], ["r"])   # 3rd touch, on target
    assert cache.category_count("a") == 1
    res = cache.lookup_batch(emb, ["a"])
    assert res[0].hit and res[0].response == "r"


# ---------------------------------------------------------------------------
# Planner placement.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_shards", [2, 4])
def test_planner_beats_crc32_on_paper_quotas(n_shards):
    """Quota-byte bin-packing spreads the Table-1 quota mass strictly
    better (max/mean shard bytes) than crc32-mod, which piles the head
    categories onto one shard."""
    pol = PolicyEngine(paper_policies())
    planner = ShardPlanner.from_policies(pol, n_shards, 100_000)
    crc_bytes = [0] * n_shards
    for name in pol.categories():
        crc_bytes[crc32_shard(name, n_shards)] += \
            planner.quota_bytes(pol.get(name).quota)
    crc_imbalance = max(crc_bytes) / (sum(crc_bytes) / n_shards)
    assert planner.imbalance() < crc_imbalance
    # LPT is bound below by the single heaviest category (code_generation
    # holds 0.40 of the quota mass — replication, not placement, would be
    # needed to split it; see ROADMAP open items), so the achievable
    # spread depends on the shard count.
    assert planner.imbalance() <= {2: 1.1, 4: 1.65}[n_shards]
    # deterministic: replanning produces the identical assignment
    again = ShardPlanner.from_policies(pol, n_shards, 100_000)
    assert again.assignments == planner.assignments


def test_planner_weights_follow_residency_dtype():
    """int8 residency shrinks every quota-byte weight (the embedding
    component ~4x; graph + metadata ride along unshrunk, so the whole
    entry lands ~2.8x at d=384) and preserves the relative packing."""
    fp32 = ResidencyModel(dim=384, emb_dtype="float32")
    int8 = ResidencyModel(dim=384, emb_dtype="int8")
    assert fp32.quota_bytes(0.4, 10_000) > 2.5 * int8.quota_bytes(0.4, 10_000)
    pol = PolicyEngine(paper_policies())
    a = ShardPlanner.from_policies(pol, 4, 50_000, emb_dtype="float32")
    b = ShardPlanner.from_policies(pol, 4, 50_000, emb_dtype="int8")
    assert a.assignments == b.assignments


def test_planner_unknown_category_and_assign():
    pol = _policies()
    planner = ShardPlanner.from_policies(pol, 2, 1000)
    s = planner.shard_of("never_seen")          # registers on first sight
    assert planner.shard_of("never_seen") == s
    planner.assign("a", 1 - planner.shard_of("a"))
    assert sum(planner.shard_bytes) == sum(planner._bytes.values())


def test_router_shard_for_uses_planner_with_hash_fallback():
    from repro.serving.router import ModelBackend, ModelRouter
    pol = PolicyEngine(paper_policies())
    backends = [ModelBackend("m", 100.0, 0.01)]
    routed = ModelRouter(pol, backends, n_cache_shards=2)
    assert routed.planner is not None
    heads = ("code_generation", "api_documentation")
    assert routed.shard_for(heads[0]) != routed.shard_for(heads[1])
    fallback = ModelRouter(PolicyEngine(paper_policies()), backends,
                           n_cache_shards=2, planner=False)
    assert fallback.planner is None
    for name in pol.categories():
        assert fallback.shard_for(name) == crc32_shard(name, 2)
    # crc32 collides the heads — the failure mode the planner removes
    assert fallback.shard_for(heads[0]) == fallback.shard_for(heads[1])


# ---------------------------------------------------------------------------
# Live category migration.
# ---------------------------------------------------------------------------

def _migration_cache(emb_dtype="float32", index_kind="flat",
                     use_device=False):
    pol = PolicyEngine([
        CategoryConfig("a", threshold=0.80, ttl=500.0, quota=0.45,
                       priority=2.0),
        CategoryConfig("b", threshold=0.80, ttl=1e6, quota=0.45),
    ])
    planner = ShardPlanner(2, 256, residency=ResidencyModel(
        dim=DIM, emb_dtype=emb_dtype), policies=pol)
    planner.plan({"a": 0.45, "b": 0.45})        # a → shard 0, b → shard 1
    return ShardedSemanticCache(pol, dim=DIM, capacity=256, n_shards=2,
                                clock=SimClock(), index_kind=index_kind,
                                use_device=use_device, emb_dtype=emb_dtype,
                                planner=planner, seed=3)


@pytest.mark.parametrize("emb_dtype,index_kind,use_device", [
    ("float32", "flat", False),
    ("int8", "flat", True),
    ("float32", "hnsw", True),
    ("int8", "hnsw", True),
])
def test_live_migration_coherence(emb_dtype, index_kind, use_device):
    """Mid-migration reads stay correct (source serves until cutover),
    writes during the drain are caught up, and after cutover the target
    holds every entry exactly once — timestamps, hit counts and (under
    int8) the quantized rows preserved bit-identically."""
    cache = _migration_cache(emb_dtype, index_kind, use_device)
    banks = _banks()
    n0 = 30
    embs = banks["a"][:n0]
    cache.insert_batch(embs, ["a"] * n0,
                       [f"q{i}" for i in range(n0)],
                       [f"r{i}" for i in range(n0)])
    cache.insert_batch(banks["b"][:10], ["b"] * 10,
                       [f"bq{i}" for i in range(10)],
                       [f"br{i}" for i in range(10)])
    t_inserted = cache.shards[0].slot_inserted[
        cache.shards[0].category_slots("a")].copy()
    cache.clock.advance(5.0)

    src, dst = cache.shard_of("a"), cache.shard_of("b")
    assert src != dst
    mig = cache.migrate_category("a", dst, batch_size=7, stepwise=True)
    total_new = 0
    while mig.remaining() > 0:
        mig.step()
        # reads mid-drain: every entry (old and mid-drain-written) hits
        # with its own document, and "a" still routes to the source
        assert cache.shard_of("a") == src
        res = cache.lookup_batch(embs[:n0], ["a"] * n0)
        for i, r in enumerate(res):
            assert r.hit and r.response == f"r{i}"
        # writes DURING the drain — they land on the source (each one
        # re-fills the pending set) and must survive the cutover catch-up
        if total_new < 4:
            i = n0 + total_new
            cache.insert_batch(banks["a"][i][None, :], ["a"],
                               [f"q{i}"], [f"r{i}"])
            total_new += 1
    assert total_new == 4
    mig.cutover()

    n = n0 + total_new
    assert cache.shard_of("a") == dst
    assert cache.shards[src].category_count("a") == 0
    assert cache.shards[dst].category_count("a") == n   # no loss, no dupes
    res = cache.lookup_batch(banks["a"][:n], ["a"] * n)
    for i, r in enumerate(res):
        assert r.hit and r.response == f"r{i}"
    # b never moved and never flinched
    res_b = cache.lookup_batch(banks["b"][:10], ["b"] * 10)
    assert all(r.hit for r in res_b)

    # preserved state on the target: timestamps (ages), quantized rows
    dslots = cache.shards[dst].category_slots("a")
    migrated_ts = np.sort(cache.shards[dst].slot_inserted[dslots])[:n0]
    assert np.array_equal(migrated_ts, np.sort(t_inserted))
    if emb_dtype == "int8":
        idx = cache.shards[dst].index
        q, s = quantize_rows(idx.emb[dslots])
        assert np.array_equal(idx.emb_q[dslots], q)
        assert np.array_equal(idx.emb_scale[dslots], s)
    # preserved timestamps keep TTL semantics: the originals expire on
    # the TARGET exactly when they would have on the source
    cache.clock.advance(500.0)
    res = cache.lookup_batch(embs[:5], ["a"] * 5)
    assert all(r.reason == "expired" for r in res)


def test_migration_reconciles_source_evictions_and_hits():
    """Entries evicted from the source AFTER being copied do not
    resurrect at cutover, and hits served during the drain transfer."""
    cache = _migration_cache()
    banks = _banks()
    cache.insert_batch(banks["a"][:12], ["a"] * 12,
                       [f"q{i}" for i in range(12)],
                       [f"r{i}" for i in range(12)])
    src, dst = cache.shard_of("a"), 1 - cache.shard_of("a")
    mig = cache.migrate_category("a", dst, batch_size=12, stepwise=True)
    assert mig.step() == 12                     # everything copied
    # source-side eviction after the copy (TTL) + hits during the drain
    s0 = cache.shards[src]
    victims = s0.category_slots("a")[:3]
    victim_docs = {f"r{int(np.argmax(banks['a'][:12] @ s0.index.emb[v]))}"
                   for v in victims}
    for v in victims:
        s0._evict_slot(int(v), reason="ttl")
    cache.lookup_batch(banks["a"][3:8], ["a"] * 5)   # hits accrue on src
    mig.cutover()
    assert cache.shards[dst].category_count("a") == 9
    res = cache.lookup_batch(banks["a"][:12], ["a"] * 12)
    served = {r.response for r in res if r.hit}
    assert len(served) == 9 and served.isdisjoint(victim_docs)
    # drain-time hits carried over to the target's eviction scoring
    hit_slots = cache.shards[dst].category_slots("a")
    assert cache.shards[dst].slot_hits[hit_slots].sum() >= 5


def test_migration_reconcile_keeps_the_victim_index():
    """Hits the source served during the drain reach the target's victim
    index at the reconcile: both shards' indexes agree with the brute
    force, and the target's quota victims are the brute force's."""
    cache = _migration_cache()
    banks = _banks(160)
    cache.insert_batch(banks["a"][:40], ["a"] * 40,
                       [f"q{i}" for i in range(40)],
                       [f"r{i}" for i in range(40)])
    src, dst = cache.shard_of("a"), 1 - cache.shard_of("a")
    mig = cache.migrate_category("a", dst, batch_size=40, stepwise=True)
    assert mig.step() == 40
    cache.clock.advance(1.0)
    cache.lookup_batch(banks["a"][5:25], ["a"] * 20)
    cache.lookup_batch(banks["a"][5:15], ["a"] * 10)
    mig.cutover()
    target = cache.shards[dst]
    assert target.slot_hits[target.category_slots("a")].max() == 2
    for sh in cache.shards:
        assert_index_agrees(sh)
    rec = VictimRecorder(target)
    cache.clock.advance(1.0)
    for lo in range(40, 160, 20):
        cache.insert_batch(banks["a"][lo:lo + 20], ["a"] * 20,
                           [f"q{i}" for i in range(lo, lo + 20)],
                           [f"r{i}" for i in range(lo, lo + 20)])
        cache.clock.advance(0.5)
    assert len(rec.got) >= 20 and rec.got == rec.want
    assert_index_agrees(target)


def test_rebalance_follows_quota_reassignment():
    """Quota changes re-plan placement and live-migrate the movers —
    the AdaptiveController-shaped trigger."""
    pol = PolicyEngine([
        CategoryConfig("big", threshold=0.80, ttl=1e6, quota=0.40),
        CategoryConfig("mid", threshold=0.80, ttl=1e6, quota=0.30),
        CategoryConfig("small", threshold=0.80, ttl=1e6, quota=0.10),
    ])
    cache = ShardedSemanticCache(pol, dim=DIM, capacity=256, n_shards=2,
                                 clock=SimClock(), index_kind="flat")
    banks = _banks()
    # seed entries for every category (reuse bank "a" vectors, distinct
    # intents per category so embeddings never collide across them)
    names = ["big", "mid", "small"]
    for k, name in enumerate(names):
        vecs = banks["a"][10 * k:10 * k + 8]
        cache.insert_batch(vecs, [name] * 8,
                           [f"{name}q{i}" for i in range(8)],
                           [f"{name}r{i}" for i in range(8)])
    before = {n: cache.shard_of(n) for n in names}
    # invert the economics: "small" becomes the heavy category
    pol.update("big", quota=0.05)
    pol.update("small", quota=0.45)
    moves = cache.rebalance()
    assert moves, "rebalance made no moves despite inverted quotas"
    for name, (s, d) in moves.items():
        assert before[name] == s and cache.shard_of(name) == d
    for k, name in enumerate(names):
        vecs = banks["a"][10 * k:10 * k + 8]
        res = cache.lookup_batch(vecs, [name] * 8)
        assert all(r.hit for r in res), f"{name} lost entries in rebalance"


def test_migration_guards():
    cache = _migration_cache()
    assert cache.migrate_category("a", cache.shard_of("a")) is None
    assert cache.migrate_category("a", 99) is None
    mig = cache.migrate_category("a", 1, stepwise=True)
    with pytest.raises(RuntimeError):
        cache.migrate_category("a", 1)
    mig.cutover()
    assert "a" not in cache._migrations
    assert isinstance(mig, CategoryMigration)


def test_doc_id_of_invalid_slot():
    """INVALID slots decode to INVALID on both cache types — never to a
    real shard/slot via numpy negative indexing."""
    single = SemanticCache(_policies(), dim=DIM, capacity=8,
                           clock=SimClock(), index_kind="flat")
    banks = _banks()
    single.insert_batch(banks["a"][:8], ["a"] * 8,
                        [f"q{i}" for i in range(8)],
                        [f"r{i}" for i in range(8)])     # fill every slot
    assert single.doc_id_of(INVALID) == INVALID
    sharded = ShardedSemanticCache(_policies(), dim=DIM, capacity=8,
                                   n_shards=2, clock=SimClock(),
                                   index_kind="flat")
    sharded.insert_batch(banks["a"][:4], ["a"] * 4,
                         [f"q{i}" for i in range(4)],
                         [f"r{i}" for i in range(4)])
    assert sharded.shard_of_slot(INVALID) == (INVALID, INVALID)
    assert sharded.doc_id_of(INVALID) == INVALID


def test_migration_into_full_target_aborts_cleanly():
    """A drain step that finds the target physically full aborts the
    migration atomically: no target copies survive, the source keeps
    serving, and the move is retryable (not stuck in _migrations)."""
    pol = PolicyEngine([
        CategoryConfig("a", threshold=0.80, ttl=1e6, quota=0.45),
        CategoryConfig("b", threshold=0.80, ttl=1e6, quota=0.45),
    ])
    planner = ShardPlanner(2, 40, policies=pol)
    planner.plan({"a": 0.45, "b": 0.45})
    cache = ShardedSemanticCache(pol, dim=DIM, capacity=40, n_shards=2,
                                 clock=SimClock(), index_kind="flat",
                                 planner=planner, shard_capacity=12)
    banks = _banks()
    cache.insert_batch(banks["a"][:10], ["a"] * 10,
                       [f"q{i}" for i in range(10)],
                       [f"r{i}" for i in range(10)])
    cache.insert_batch(banks["b"][:10], ["b"] * 10,
                       [f"bq{i}" for i in range(10)],
                       [f"br{i}" for i in range(10)])    # target nearly full
    with pytest.raises(RuntimeError, match="free"):
        cache.migrate_category("a", cache.shard_of("b"), batch_size=5)
    assert "a" not in cache._migrations                  # retryable
    assert cache.shards[cache.shard_of("b")].category_count("a") == 0
    res = cache.lookup_batch(banks["a"][:10], ["a"] * 10)
    assert all(r.hit for r in res)                       # source untouched
    with pytest.raises(RuntimeError, match="free"):      # retry, same error
        cache.migrate_category("a", cache.shard_of("b"))


def test_rebalance_requires_shard_planner():
    cache = ShardedSemanticCache(_policies(), dim=DIM, capacity=64,
                                 n_shards=2, clock=SimClock(),
                                 index_kind="flat", planner=CRC32Planner(2))
    with pytest.raises(TypeError, match="ShardPlanner"):
        cache.rebalance()


def test_crc32_planner_is_the_hash():
    p = CRC32Planner(4)
    assert p.shard_of("code_generation") == crc32_shard("code_generation", 4)
    p.assign("code_generation", 2)
    assert p.shard_of("code_generation") == 2
