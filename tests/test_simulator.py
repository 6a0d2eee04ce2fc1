"""End-to-end simulator behavior: Table 1 bands, baseline comparisons,
adaptive load reduction, staleness/TTL trade-offs, the deterministic
scenario matrix, and hit/miss accounting under admission control."""

import numpy as np
import pytest

from repro.core.policy import PolicyEngine, paper_policies
from repro.core.workload import (SCENARIO_NAMES, TABLE1_WORKLOAD,
                                 WorkloadGenerator, scenario_generator,
                                 scenario_matrix)
from repro.serving.simulator import ServingSimulator, SimConfig

N = 5000


def run(arch="hybrid", n=N, adaptive=False, spikes=(), seed=42, **kw):
    eng = PolicyEngine(paper_policies())
    gen = WorkloadGenerator(TABLE1_WORKLOAD, rate_per_s=30.0, seed=seed)
    sim = ServingSimulator(eng, SimConfig(
        architecture=arch, cache_capacity=12000, index_kind="flat",
        adaptive=adaptive, load_spikes=list(spikes), **kw))
    return sim.run(gen, n)


@pytest.fixture(scope="module")
def hybrid_result():
    return run("hybrid")


@pytest.fixture(scope="module")
def none_result():
    return run("none")


def test_long_tail_hit_rate_bands(hybrid_result):
    """Table 1 qualitative claim: head 40–60 %+, tail 2–20 %."""
    pc = hybrid_result.per_category
    assert pc["code_generation"]["hit_rate"] > 0.40
    assert pc["api_documentation"]["hit_rate"] > 0.35
    for tail in ("conversational_chat", "financial_data", "legal_queries",
                 "medical_queries", "specialized_domains"):
        assert 0.005 <= pc[tail]["hit_rate"] <= 0.25, (tail, pc[tail])
    head = pc["code_generation"]["hit_rate"]
    tail = pc["conversational_chat"]["hit_rate"]
    assert head > 2.5 * tail                     # long tail shape


def test_hybrid_beats_none_latency(hybrid_result, none_result):
    assert hybrid_result.mean_latency_ms < none_result.mean_latency_ms
    assert hybrid_result.model_cost < none_result.model_cost


def test_hybrid_beats_vdb_on_heterogeneous_workload(hybrid_result):
    vdb = run("vdb")
    # Uniform collection threshold (0.85) mismatches the dense code space
    # (cross-intent sims ≈ 0.85): the vdb "hits" are contaminated with
    # false positives — wrong answers served fast (§3.1/§4.2).
    assert vdb.false_positives > 5 * max(1, hybrid_result.false_positives)
    # Quality-adjusted latency (every FP hit must be re-asked → + T_llm):
    t_fp = 500.0
    hy = hybrid_result.mean_latency_ms + \
        hybrid_result.false_positives / hybrid_result.n_queries * t_fp
    vd = vdb.mean_latency_ms + vdb.false_positives / vdb.n_queries * t_fp
    assert hy < vd
    # structural overhead claim: vdb pays 30 ms search on EVERY query
    assert vdb.mean_latency_ms > 30.0


def test_financial_ttl_limits_staleness(hybrid_result):
    """5-minute TTL on 80 %/h content keeps stale serves low."""
    fin = hybrid_result.per_category["financial_data"]
    if fin["hits"]:
        assert fin["stale_served"] / max(1, fin["hits"]) < 0.35


def test_compliance_category_never_cached():
    from dataclasses import replace
    from repro.core.workload import CategorySpec
    specs = TABLE1_WORKLOAD + [CategorySpec(
        "phi_medical_records", traffic_share=0.05, pool_size=100,
        zipf_alpha=1.5, staleness_per_s=0.0, t_llm_ms=300.0,
        model_name="gpt4o", sigma=0.01, center_spread=0.3, seed=99)]
    total = sum(s.traffic_share for s in specs)
    specs = [replace(s, traffic_share=s.traffic_share / total) for s in specs]
    eng = PolicyEngine(paper_policies())
    gen = WorkloadGenerator(specs, rate_per_s=30.0, seed=7)
    sim = ServingSimulator(eng, SimConfig(architecture="hybrid",
                                          index_kind="flat"))
    res = sim.run(gen, 2000)
    phi = res.per_category.get("phi_medical_records")
    assert phi is not None
    assert phi["hits"] == 0
    assert phi["compliance_rejects"] == phi["lookups"]


def test_adaptive_reduces_model_traffic_under_load():
    """§7.5: threshold relaxation under a spike cuts model calls for the
    loaded model vs the non-adaptive run (projection band: >0 %, sane)."""
    spikes = [(30.0, 900.0, "o1", 3.0)]
    base = run("hybrid", adaptive=False, spikes=spikes, seed=11)
    adap = run("hybrid", adaptive=True, spikes=spikes, seed=11)
    calls_base = base.model_calls.get("o1", 0)
    calls_adap = adap.model_calls.get("o1", 0)
    assert calls_adap < calls_base
    reduction = 1 - calls_adap / calls_base
    assert 0.005 <= reduction <= 0.5, reduction


def test_false_positive_rates_with_wrong_threshold():
    """§3.1: τ=0.80 on dense code space → cross-intent false positives;
    the category-aware τ=0.90 suppresses them."""
    eng_bad = PolicyEngine(paper_policies())
    eng_bad.update("code_generation", threshold=0.80)
    gen = WorkloadGenerator(TABLE1_WORKLOAD, rate_per_s=30.0, seed=5)
    sim = ServingSimulator(eng_bad, SimConfig(architecture="hybrid",
                                              index_kind="flat"))
    res_bad = sim.run(gen, 3000)
    fp_bad = res_bad.per_category["code_generation"]["fp_rate"]

    res_good = run("hybrid", n=3000, seed=5)
    fp_good = res_good.per_category["code_generation"]["fp_rate"]
    assert fp_bad > fp_good
    assert fp_bad > 0.02
    assert fp_good < 0.02


# ---------------------------------------------------------------------------
# Scenario matrix (core/workload.py): deterministic generation, shape
# sanity, and simulator smoke per scenario.
# ---------------------------------------------------------------------------

def test_scenario_matrix_registry():
    mat = scenario_matrix()
    assert tuple(mat) == SCENARIO_NAMES
    assert {"power_law", "uniform_tail", "bursty", "drifting",
            "session_drift", "flash_crowd", "stale_burst"} == set(mat)
    for name, scen in mat.items():
        assert scen.name == name and scen.description
        assert sum(s.traffic_share for s in scen.specs) == \
            pytest.approx(1.0)
    with pytest.raises(KeyError):
        scenario_generator("no_such_scenario")
    # rate override reaches the generator
    gen = scenario_generator("power_law", seed=1, rate_per_s=100.0)
    assert gen.rate_per_s == 100.0


@pytest.mark.parametrize("name", SCENARIO_NAMES)
def test_scenario_fixed_seed_identical_trace(name):
    """Same seed → byte-identical query trace (category, intent,
    timestamp, version AND embedding); a different seed diverges."""
    a = scenario_generator(name, seed=3).generate(200)
    b = scenario_generator(name, seed=3).generate(200)
    for qa, qb in zip(a, b):
        assert (qa.category, qa.intent_id, qa.content_version,
                qa.timestamp) == \
            (qb.category, qb.intent_id, qb.content_version, qb.timestamp)
        assert np.array_equal(qa.embedding, qb.embedding)
    c = scenario_generator(name, seed=4).generate(200)
    assert any(qa.intent_id != qc.intent_id or qa.category != qc.category
               for qa, qc in zip(a, c))


def test_power_law_vs_uniform_tail_shape():
    """The two gate scenarios sit at opposite ends of the repetition
    spectrum: Zipf code traffic concentrates (top-10 intents ≫ uniform's)
    while the 50 k-intent chat tail almost never repeats."""
    from collections import Counter
    pl = Counter(q.intent_id
                 for q in scenario_generator("power_law", seed=3)
                 .generate(2000))
    ut = Counter(q.intent_id
                 for q in scenario_generator("uniform_tail", seed=3)
                 .generate(2000))
    top10 = lambda c: sum(n for _, n in c.most_common(10)) / 2000  # noqa: E731
    assert top10(pl) > 0.30 and len(pl) / 2000 < 0.45
    assert top10(ut) < 0.08 and len(ut) / 2000 > 0.75


def test_bursty_rotating_working_set():
    """Within the first burst window, ≥70 % of draws land in the 32-
    intent working set starting at intent 0 (burst_frac = 0.85 minus the
    uniform escape traffic)."""
    qs = scenario_generator("bursty", seed=3).generate(1000)
    w0 = [q for q in qs if q.timestamp < 60.0]
    assert len(w0) > 500
    share = sum(1 for q in w0 if 0 <= q.intent_id < 32) / len(w0)
    assert share > 0.70


def test_drifting_head_slides_with_time():
    """The Zipf head tracks a center moving at drift_per_s: the median
    intent of the last 500 queries sits far above the first 500's."""
    import statistics
    qs = scenario_generator("drifting", seed=3).generate(4000)
    first = statistics.median(q.intent_id for q in qs[:500])
    last = statistics.median(q.intent_id for q in qs[-500:])
    assert last > first + 100


def test_flash_crowd_is_windowed():
    """Chat traffic concentrates on the 16 flash intents ONLY inside
    the [20 s, 80 s) flash span."""
    qs = scenario_generator("flash_crowd", seed=3).generate(3000)
    chat = [q for q in qs if q.category == "conversational_chat"]
    inw = [q for q in chat if 20.0 <= q.timestamp < 80.0]
    outw = [q for q in chat if not (20.0 <= q.timestamp < 80.0)]
    assert len(inw) > 200 and len(outw) > 200
    assert sum(q.intent_id < 16 for q in inw) / len(inw) > 0.30
    assert sum(q.intent_id < 16 for q in outw) / len(outw) < 0.05


def _scenario_run(name, n=400, gated=None, eviction="static", seed=0):
    pol = PolicyEngine(paper_policies())
    if gated:
        pol.update(gated, admit_after=2)
    sim = ServingSimulator(pol, SimConfig(
        architecture="hybrid", cache_capacity=3000, index_kind="flat",
        eviction=eviction, seed=seed))
    return sim.run(scenario_generator(name, seed=seed), n), sim


@pytest.mark.parametrize("name", SCENARIO_NAMES)
def test_simulator_smoke_every_scenario(name):
    """Every scenario drives the hybrid simulator end to end, and the
    counters balance: category lookups sum to queries issued and
    hits + misses == lookups in every category."""
    res, _ = _scenario_run(name)
    assert res.n_queries == 400
    assert sum(s["lookups"] for s in res.per_category.values()) == 400
    for cat, s in res.per_category.items():
        assert s["hits"] + s["misses"] == s["lookups"], (name, cat, s)
    assert res.mean_resident_entries > 0
    assert res.hits_per_resident_mb >= 0.0


def test_admission_skips_are_not_a_hit_rate_leak():
    """Accounting regression (the admission gate must not perturb the
    lookup ledger): with admit-on-2nd-touch active on chat, lookups
    still sum to queries issued, hits + misses == lookups, the skips
    surface in cache metrics, and the insert-side stats balance."""
    res, sim = _scenario_run("uniform_tail", n=1500,
                             gated="conversational_chat")
    per = res.metrics.per_category
    assert sum(s.lookups for s in per.values()) == 1500
    for s in per.values():
        assert s.hits + s.misses == s.lookups
    chat = per["conversational_chat"]
    assert chat.admission_skips > 0
    # skips are misses that were simply not admitted — never hits, and
    # never more numerous than the misses that produced them
    assert chat.admission_skips <= chat.misses
    # the serialized view and the insert-side ledger agree
    assert res.per_category["conversational_chat"]["admission_skips"] \
        == chat.admission_skips
    ins = sim.cache.last_insert_stats
    assert ins["batch"] == ins["admitted"] + ins["admission_skips"] \
        + ins["insert_rejects"]
    # an ungated run of the same scenario records zero skips
    res2, _ = _scenario_run("uniform_tail", n=1500)
    assert all(s["admission_skips"] == 0
               for s in res2.per_category.values())
    # and gating strictly shrinks the resident footprint
    assert res.mean_resident_entries < res2.mean_resident_entries


def test_sample_entries_matches_the_mix():
    """The bulk sampler draws the Table-1 mix: category shares near the
    traffic shares, unit-norm paraphrases near their intent's center,
    and the same draws for the same seed."""
    gen = WorkloadGenerator(TABLE1_WORKLOAD, seed=3)
    emb, cats, intents = gen.sample_entries(20000)
    assert emb.shape == (20000, 384) and emb.dtype == np.float32
    np.testing.assert_allclose(np.linalg.norm(emb, axis=1), 1.0, atol=1e-5)
    for spec in TABLE1_WORKLOAD:
        rows = [i for i, c in enumerate(cats) if c == spec.name]
        assert abs(len(rows) / 20000 - spec.traffic_share) < 0.02
        centers = gen.spaces[spec.name].centers[intents[rows]]
        assert np.mean(np.sum(emb[rows] * centers, axis=1)) > 0.5
    emb2, cats2, _ = WorkloadGenerator(TABLE1_WORKLOAD,
                                       seed=3).sample_entries(20000)
    assert cats2 == cats and np.array_equal(emb2, emb)
