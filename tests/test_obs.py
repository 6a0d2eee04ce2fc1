"""Observability pipeline (repro/obs): histograms, spans, accounting,
exports — plus the metrics satellites that ride with it.

The properties that matter:

* **Histogram fidelity** — fixed log-scale buckets give quantiles
  within one bucket width (~9% relative) of numpy's, means are exact,
  and merge is equivalent to observing the union.
* **Span accounting** — under ``SimClock`` every opened span closes
  and, for every root, leaf-descendant durations sum to the root
  duration exactly (all clock charges live in leaf spans). Leaks and
  gaps are detected, not silently absorbed.
* **Empty-recorder parity** — tracing OFF is bit-identical to the
  untraced build, and tracing ON changes no counter either (it only
  observes). Mirrors the fault injector's empty-schedule discipline.
* **Attribution** — a fault scenario's degraded windows are fully
  explained by ``degraded_accrue`` events; one ``degraded_miss`` event
  per counted degraded lookup.
* **Span lint** — any ``clock.advance`` in a traced module without a
  span (or pragma) is a static violation; the real tree is clean.
"""

import json
import math

import numpy as np
import pytest

from repro.analysis import span_lint
from repro.core import SemanticCache, ShardedSemanticCache, SimClock
from repro.core.faults import FaultSchedule
from repro.core.metrics import CategoryStats, MetricsRegistry, overall_row
from repro.core.policy import (CategoryConfig, PolicyEngine,
                               paper_policies)
from repro.core.workload import scenario_generator
from repro.obs import (LatencyHistogram, TraceRecorder,
                       check_span_accounting, coverage_fraction,
                       prometheus_text, span_accounting, telemetry_report)
from repro.obs.hist import (GROWTH, HistogramSet, bucket_of,
                            bucket_upper_ms)
from repro.obs.trace import NO_PARENT
from repro.serving.simulator import ServingSimulator, SimConfig

DIM = 48


def _policies() -> PolicyEngine:
    return PolicyEngine([
        CategoryConfig("a", threshold=0.80, ttl=1e6, quota=0.5),
        CategoryConfig("b", threshold=0.78, ttl=1e6, quota=0.5),
    ])


def _bank(seed: int, n: int = 32) -> np.ndarray:
    rng = np.random.default_rng(seed)
    v = rng.standard_normal((n, DIM)).astype(np.float32)
    return v / np.linalg.norm(v, axis=1, keepdims=True)


# ---------------------------------------------------------------- histogram
class TestLatencyHistogram:
    def test_bucket_edges_bracket_sample(self):
        for ms in (1e-4, 1e-3, 0.0123, 1.0, 2.0, 37.5, 1e4, 1e6):
            i = bucket_of(ms)
            assert ms <= bucket_upper_ms(i) or i == bucket_of(1e9)
            if i > 0 and bucket_upper_ms(i) != math.inf:
                lower = bucket_upper_ms(i) / GROWTH
                assert lower < ms <= bucket_upper_ms(i)

    def test_quantiles_within_bucket_tolerance_of_numpy(self):
        rng = np.random.default_rng(0)
        samples = rng.lognormal(mean=1.0, sigma=1.2, size=5000)
        h = LatencyHistogram()
        for s in samples:
            h.observe(float(s))
        for q in (0.50, 0.95, 0.99):
            exact = float(np.quantile(samples, q))
            approx = h.quantile(q)
            # one bucket of relative error (geometric midpoint)
            assert exact / GROWTH <= approx <= exact * GROWTH

    def test_mean_is_exact_and_minmax_tracked(self):
        h = LatencyHistogram()
        vals = [0.5, 2.0, 8.0, 32.0]
        for v in vals:
            h.observe(v)
        assert h.mean_ms == pytest.approx(sum(vals) / len(vals), abs=0)
        assert h.min_ms == 0.5 and h.max_ms == 32.0
        assert h.count == 4

    def test_merge_equivalent_to_union(self):
        rng = np.random.default_rng(1)
        a, b = LatencyHistogram(), LatencyHistogram()
        both = LatencyHistogram()
        for v in rng.lognormal(size=400):
            a.observe(float(v))
            both.observe(float(v))
        for v in rng.lognormal(size=300):
            b.observe(float(v))
            both.observe(float(v))
        a.merge(b)
        assert a.counts == both.counts
        assert a.count == both.count == 700
        assert a.sum_ms == pytest.approx(both.sum_ms)
        assert a.quantile(0.95) == both.quantile(0.95)

    def test_to_dict_shape(self):
        h = LatencyHistogram()
        h.observe(1.5)
        d = h.to_dict()
        assert d["count"] == 1 and d["sum_ms"] == 1.5
        assert list(d["buckets"].values()) == [1]

    def test_empty_quantile_is_zero(self):
        assert LatencyHistogram().quantile(0.99) == 0.0
        assert LatencyHistogram().mean_ms == 0.0

    def test_histogram_set_rollup(self):
        hs = HistogramSet()
        hs.observe("search", 1.0, category="a", shard=0)
        hs.observe("search", 2.0, category="b", shard=1)
        hs.observe("write", 4.0, category="a", shard=0)
        assert hs.stages() == ["search", "write"]
        assert hs.rollup(stage="search").count == 2
        assert hs.rollup(category="a").count == 2
        assert hs.rollup(stage="search", shard=1).count == 1
        assert hs.rollup().sum_ms == pytest.approx(7.0)
        assert len(hs.to_dict()) == 3


# ---------------------------------------------------------------- recorder
class TestTraceRecorder:
    def test_nesting_parent_ids_and_simclock_durations(self):
        clock = SimClock()
        rec = TraceRecorder(clock)
        with rec.span("root", category="a"):
            with rec.span("leaf1"):
                clock.advance(0.002)
            with rec.span("leaf2"):
                clock.advance(0.003)
        root, l1, l2 = rec.spans
        assert root.parent_id == NO_PARENT
        assert l1.parent_id == root.span_id == l2.parent_id
        assert l1.dur_ms == pytest.approx(2.0)
        assert l2.dur_ms == pytest.approx(3.0)
        assert root.dur_ms == pytest.approx(5.0)
        assert rec.opened == rec.closed == 3
        assert check_span_accounting(rec) == []
        assert coverage_fraction(rec) == pytest.approx(1.0)

    def test_span_closes_on_exception(self):
        clock = SimClock()
        rec = TraceRecorder(clock)
        with pytest.raises(RuntimeError):
            with rec.span("root"):
                clock.advance(0.001)
                raise RuntimeError("boom")
        assert rec.opened == rec.closed == 1
        assert rec.spans[0].dur_ms == pytest.approx(1.0)

    def test_leak_detected(self):
        rec = TraceRecorder(SimClock())
        rec.span("never_closed")            # no `with`, never exits
        out = check_span_accounting(rec)
        assert any("span leak" in v for v in out)

    def test_charge_outside_leaf_detected_as_gap(self):
        clock = SimClock()
        rec = TraceRecorder(clock)
        with rec.span("root"):
            with rec.span("leaf"):
                clock.advance(0.001)
            clock.advance(0.004)            # un-spanned: breaks accounting
        acc = span_accounting(rec)
        assert acc["gapped_roots"] and acc["max_gap_ms"] == pytest.approx(4.0)
        assert check_span_accounting(rec)
        assert coverage_fraction(rec) == pytest.approx(0.2)

    def test_events_and_counts(self):
        rec = TraceRecorder(SimClock())
        rec.event("eviction", reason="quota", category="a")
        rec.event("eviction", reason="ttl", category="b")
        rec.event("wb_enqueue", shard=1)
        assert rec.event_counts() == {"eviction": 2, "wb_enqueue": 1}
        assert rec.events[0].fields["reason"] == "quota"

    def test_childless_root_counts_its_own_duration(self):
        clock = SimClock()
        rec = TraceRecorder(clock)
        with rec.span("solo"):
            clock.advance(0.002)
        assert check_span_accounting(rec) == []


# ------------------------------------------------------- single-cache spans
class TestCacheSpans:
    def test_lookup_and_insert_span_structure(self):
        clock = SimClock()
        rec = TraceRecorder(clock)
        cache = SemanticCache(_policies(), dim=DIM, capacity=64,
                              clock=clock, seed=0, obs=rec)
        v = _bank(0, 8)
        cache.insert_batch(v, ["a"] * 8, [f"q{i}" for i in range(8)],
                           [f"r{i}" for i in range(8)])
        cache.lookup_batch(v[:4], ["a"] * 4)
        stages = {sp.stage for sp in rec.spans}
        assert {"insert", "gate", "write", "lookup", "search"} <= stages
        roots = [sp for sp in rec.spans if sp.parent_id == NO_PARENT]
        assert {sp.stage for sp in roots} == {"insert", "lookup"}
        assert check_span_accounting(rec) == []
        # store_fetch leaves fire on resolved hits
        assert any(sp.stage == "store_fetch" for sp in rec.spans)

    def test_eviction_event_emitted(self):
        clock = SimClock()
        rec = TraceRecorder(clock)
        cache = SemanticCache(_policies(), dim=DIM, capacity=8,
                              clock=clock, seed=0, obs=rec)
        v = _bank(1, 24)
        # two batches: the second must evict MATERIALIZED entries (same-
        # batch quota pressure only drops pending items, no slot evicted)
        for lo in (0, 12):
            cache.insert_batch(v[lo:lo + 12], ["a"] * 12,
                               [f"q{lo + i}" for i in range(12)],
                               [f"r{lo + i}" for i in range(12)])
        evc = rec.event_counts()
        assert evc.get("eviction", 0) > 0
        assert check_span_accounting(rec) == []


# ------------------------------------------------------- simulator parity
def _sim_cfg(trace: bool, schedule=None, **kw) -> SimConfig:
    return SimConfig(architecture="hybrid", cache_capacity=1500,
                     n_shards=2, seed=0, fault_schedule=schedule,
                     trace=trace, **kw)


def _run(cfg, n=400):
    sim = ServingSimulator(PolicyEngine(paper_policies()), cfg)
    return sim.run(scenario_generator("flash_crowd", seed=0), n)


class TestTracingParity:
    def test_tracing_off_and_on_are_counter_identical(self):
        sched = FaultSchedule(shard_outages=[(2.0, 5.0, 0)])
        off = _run(_sim_cfg(False, sched))
        on = _run(_sim_cfg(True, sched))
        assert off.metrics.snapshot() == on.metrics.snapshot()
        assert off.mean_latency_ms == on.mean_latency_ms
        assert off.p95_latency_ms == on.p95_latency_ms
        assert off.fault_stats == on.fault_stats
        assert off.index_sync == on.index_sync
        assert off.trace is None and on.trace is not None

    def test_traced_fault_run_closes_accounting_and_attributes(self):
        sched = FaultSchedule(shard_outages=[(2.0, 6.0, 0)],
                              store_get_failures=FaultSchedule.op_range(
                                  5, 2))
        res = _run(_sim_cfg(True, sched))
        rec = res.trace
        assert check_span_accounting(rec) == []
        assert coverage_fraction(rec) == pytest.approx(1.0)
        per = res.metrics.per_category
        accrued = {}
        for ev in rec.events:
            if ev.name == "degraded_accrue":
                c = ev.fields["category"]
                accrued[c] = accrued.get(c, 0.0) + ev.fields["seconds"]
        for name, st in per.items():
            if st.degraded_seconds > 0:
                assert accrued.get(name, 0.0) == pytest.approx(
                    st.degraded_seconds, rel=1e-9), name
        deg_events = sum(1 for ev in rec.events
                         if ev.name == "degraded_miss")
        assert deg_events == sum(s.degraded_misses for s in per.values())
        assert deg_events > 0

    def test_migration_records_spans_and_closes(self):
        clock = SimClock()
        rec = TraceRecorder(clock)
        cache = ShardedSemanticCache(
            _policies(), dim=DIM, capacity=256, n_shards=2, clock=clock,
            seed=0, obs=rec)
        v = _bank(2, 24)
        cache.insert_batch(v, ["a"] * 24, [f"q{i}" for i in range(24)],
                           [f"r{i}" for i in range(24)])
        dst = 1 - cache.shard_of("a")
        cache.migrate_category("a", dst)
        stages = {sp.stage for sp in rec.spans}
        assert "migration" in stages and "migration_copy" in stages
        assert rec.event_counts().get("migration_step", 0) > 0
        assert check_span_accounting(rec) == []


# ------------------------------------------------------------- satellites
class TestMeanLatencyDenominator:
    def test_unit_served_only_denominator(self):
        st = CategoryStats(lookups=10, degraded_misses=4,
                           latency_ms_sum=60.0)
        # 6 served lookups carried the 60ms, not 10
        assert st.mean_latency_ms == pytest.approx(10.0)
        st_all = CategoryStats(lookups=10, latency_ms_sum=60.0)
        assert st_all.mean_latency_ms == pytest.approx(6.0)

    def test_all_degraded_is_zero_not_nan(self):
        st = CategoryStats(lookups=5, degraded_misses=5,
                           latency_ms_sum=0.0)
        assert st.mean_latency_ms == 0.0

    def test_outage_regression_consistent_with_hit_rate(self):
        # same denominator discipline as hit_rate: an outage must not
        # dilute the mean below what the served lookups actually paid
        sched = FaultSchedule(shard_outages=[(1.0, 8.0, 0)])
        res = _run(_sim_cfg(True, sched))
        for st in res.metrics.per_category.values():
            if not st.degraded_misses:
                continue
            served = st.lookups - st.degraded_misses
            assert st.mean_latency_ms == pytest.approx(
                st.latency_ms_sum / served if served else 0.0)


class TestOverallRow:
    def test_registry_snapshot_overall(self):
        reg = MetricsRegistry()
        a, b = reg.cat("a"), reg.cat("b")
        a.lookups, a.hits, a.misses = 10, 4, 6
        b.lookups, b.hits, b.misses, b.degraded_misses = 10, 2, 4, 4
        snap = reg.snapshot()
        ov = snap["_overall"]
        assert ov["lookups"] == 20 and ov["hits"] == 6
        # rate recomputed from summed counters (served = 20 - 4)
        assert ov["hit_rate"] == pytest.approx(6 / 16, abs=1e-4)
        assert ov["availability"] == pytest.approx(1 - 4 / 20, abs=1e-4)
        assert overall_row(reg.per_category) == ov

    def test_sharded_snapshot_overall(self):
        cache = ShardedSemanticCache(_policies(), dim=DIM, capacity=128,
                                     n_shards=2, clock=SimClock(), seed=0)
        v = _bank(3, 16)
        cache.insert_batch(v, ["a"] * 8 + ["b"] * 8,
                           [f"q{i}" for i in range(16)],
                           [f"r{i}" for i in range(16)])
        cache.lookup_batch(v, ["a"] * 8 + ["b"] * 8)
        snap = cache.metrics.snapshot()
        assert snap["_overall"]["lookups"] == \
            snap["a"]["lookups"] + snap["b"]["lookups"]
        assert snap["_overall"]["inserts"] == \
            snap["a"]["inserts"] + snap["b"]["inserts"]


class TestMetricsRoundTrips:
    def test_to_dict_fields_round_trip(self):
        st = CategoryStats(lookups=7, hits=3, misses=4, inserts=5,
                           degraded_misses=0, store_timeouts=1,
                           reranks=2, latency_ms_sum=21.0)
        d = st.to_dict()
        for k in ("lookups", "hits", "misses", "inserts",
                  "store_timeouts", "reranks"):
            assert d[k] == getattr(st, k)
        assert d["hit_rate"] == round(st.hit_rate, 4)
        assert d["mean_latency_ms"] == round(st.mean_latency_ms, 3)
        assert json.loads(json.dumps(d)) == d

    def test_slo_report_shape_and_values(self):
        cache = ShardedSemanticCache(_policies(), dim=DIM, capacity=128,
                                     n_shards=2, clock=SimClock(), seed=0)
        v = _bank(4, 8)
        cache.insert_batch(v, ["a"] * 8, [f"q{i}" for i in range(8)],
                           [f"r{i}" for i in range(8)])
        cache.lookup_batch(v, ["a"] * 8)
        rep = cache.metrics.slo_report()
        assert "a" in rep
        row = rep["a"]
        assert set(row) == {"availability", "lookups", "degraded_misses",
                            "degraded_seconds", "replicas"}
        assert row["availability"] == 1.0
        assert row["lookups"] == 8
        assert row["replicas"] >= 1


# ------------------------------------------------------------------ export
class TestExports:
    def _traced_recorder(self):
        clock = SimClock()
        rec = TraceRecorder(clock)
        with rec.span("lookup", category="a", shard=0):
            with rec.span("search", category="a", shard=0):
                clock.advance(0.002)
        rec.event("eviction", reason="quota")
        return rec

    def test_jsonl_dump_valid_and_counted(self, tmp_path):
        rec = self._traced_recorder()
        path = tmp_path / "trace.jsonl"
        n = rec.to_jsonl(path)
        lines = path.read_text().splitlines()
        assert n == len(lines) == 3              # 2 spans + 1 event
        objs = [json.loads(ln) for ln in lines]
        assert [o["type"] for o in objs] == ["span", "span", "event"]
        assert objs[1]["parent"] == objs[0]["id"]
        assert objs[1]["dur_ms"] == pytest.approx(2.0)

    def test_prometheus_exposition(self):
        rec = self._traced_recorder()
        reg = MetricsRegistry()
        reg.cat("a").lookups = 3
        text = prometheus_text(snapshot=reg.snapshot(), rec=rec)
        assert '# TYPE repro_cache_lookups counter' in text
        assert 'repro_cache_lookups{category="a"} 3' in text
        assert 'repro_cache_lookups{category="_overall"} 3' in text
        assert '# TYPE repro_stage_latency_ms histogram' in text
        assert 'le="+Inf"' in text
        assert 'repro_events_total{name="eviction"} 1' in text
        assert "repro_spans_opened_total 2" in text
        # cumulative bucket counts are monotone per series
        for series in ('stage="search"',):
            cum = [int(ln.rsplit(" ", 1)[1]) for ln in text.splitlines()
                   if "_bucket{" in ln and series in ln]
            assert cum == sorted(cum)

    def test_telemetry_report_mentions_stages_and_overall(self):
        rec = self._traced_recorder()
        reg = MetricsRegistry()
        reg.cat("a").lookups = 3
        out = telemetry_report(rec, snapshot=reg.snapshot())
        assert "search" in out and "lookup" in out
        assert "opened=2 closed=2" in out
        assert "eviction" in out
        assert "overall:" in out


# --------------------------------------------------------------- span lint
GOOD_SRC = '''
class C:
    def charged(self):
        with self._span("search"):
            self.clock.advance(0.001)
'''

BAD_SRC = '''
class C:
    def charged(self):
        self.clock.advance(0.001)
'''

PRAGMA_SRC = '''
class C:
    def charged(self):
        self.clock.advance(0.001)  # span-ok: caller-owned span
'''

PRAGMA_ABOVE_SRC = '''
class C:
    def charged(self):
        # span-ok: inter-arrival idle
        self.clock.advance(self.t - self.clock.now())
'''


class TestSpanLint:
    def test_spanned_charge_passes(self):
        assert span_lint.lint_source(GOOD_SRC) == []

    def test_unspanned_charge_flagged(self):
        out = span_lint.lint_source(BAD_SRC, filename="x.py")
        assert len(out) == 1
        assert out[0].rule == "SpanCoverage"
        assert "x.py:charged" in out[0].target

    def test_pragma_on_line_or_above_passes(self):
        assert span_lint.lint_source(PRAGMA_SRC) == []
        assert span_lint.lint_source(PRAGMA_ABOVE_SRC) == []

    def test_recorder_span_call_counts(self):
        src = GOOD_SRC.replace("self._span", "rec.span")
        assert span_lint.lint_source(src) == []

    def test_real_traced_modules_clean(self):
        assert span_lint.lint_paths() == []


# --------------------------------------------------- the profiler channel
def _profile(tmp_path, fn):
    """Run ``fn`` under a profiler trace; return the trace's events as
    (plane, line, name, start_ns, end_ns, stats)."""
    import glob

    import jax
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                            recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                out.append((plane.name, line.name, e.name, int(e.start_ns),
                            int(e.start_ns + e.duration_ns), dict(e.stats)))
    return out


def _named(events, prefix):
    return [e for e in events if e[2].startswith(prefix)]


class TestProfilerChannel:
    def test_spans_are_profiler_events_inside_bench_annotations(
            self, tmp_path):
        """Each span is a ``repro.<stage>`` host event with its scalar
        attributes as stats, nested in the enclosing ``bench.*``
        annotation, on the same ns base as the ops it waits on."""
        import jax
        import jax.numpy as jnp
        f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
        x = jnp.ones((256, 256))
        f(x).block_until_ready()
        rec = TraceRecorder(SimClock())

        def body():
            with jax.profiler.TraceAnnotation("bench.lookup_batch"):
                with rec.span("lookup", batch=7, category="a", shard=2):
                    with rec.span("device_wait", batch=7,
                                  note=[1, 2]) as sp:
                        f(x).block_until_ready()
                        sp.set(rows=3)
        ev = _profile(tmp_path, body)
        (outer,) = _named(ev, "bench.lookup_batch")
        (look,) = _named(ev, "repro.lookup")
        (wait,) = _named(ev, "repro.device_wait")
        assert look[5]["batch"] == 7 and look[5]["category"] == "a"
        assert look[5]["shard"] == 2
        assert wait[5]["batch"] == 7 and wait[5]["rows"] == 3
        assert "note" not in wait[5]         # not a scalar: left out
        assert outer[3] <= look[3] <= wait[3] <= wait[4] <= look[4] \
            <= outer[4]
        ops = [e for e in ev if "hlo_op" in e[5]]
        assert ops and all(wait[3] <= o[3] <= o[4] <= wait[4] for o in ops)
        # the recorder's own record is unchanged by the channel
        assert [sp.stage for sp in rec.spans] == ["lookup", "device_wait"]
        assert rec.spans[1].attrs == {"batch": 7, "note": [1, 2],
                                      "rows": 3}

    @pytest.mark.parametrize("traced", [False, True])
    def test_only_an_attached_recorder_emits_repro_events(self, tmp_path,
                                                           traced):
        clock = SimClock()
        rec = TraceRecorder(clock) if traced else None
        cache = SemanticCache(_policies(), dim=DIM, capacity=64,
                              clock=clock, seed=0, index_kind="flat",
                              use_device=True, obs=rec)
        v = _bank(5, 8)

        def body():
            cache.insert_batch(v, ["a"] * 8, [f"q{i}" for i in range(8)],
                               [f"r{i}" for i in range(8)])
            cache.lookup_batch(v[:4], ["a"] * 4)
        names = {e[2] for e in _named(_profile(tmp_path, body), "repro.")}
        if traced:
            assert {"repro.insert", "repro.evict", "repro.lookup",
                    "repro.search", "repro.delta_flush",
                    "repro.device_wait", "repro.clock_charge"} <= names
        else:
            assert names == set()

    def test_record_adds_a_closed_root_span(self):
        clock = SimClock(10.0)
        rec = TraceRecorder(clock)
        with rec.span("engine_step"):
            sp = rec.record("queue_wait", 7.5, 10.0, category="a", batch=1)
        assert sp.parent_id == NO_PARENT and sp.t0 == 7.5
        assert sp.dur_ms == pytest.approx(2500.0)
        assert sp.attrs == {"batch": 1} and sp.category == "a"
        assert rec.opened == rec.closed == 2
        assert rec.hist.rollup(stage="queue_wait").count == 1
        assert check_span_accounting(rec) == []


# ------------------------------------------ device-path spans and accounting
class TestDevicePathSpans:
    @pytest.mark.parametrize("index_kind", ["flat", "hnsw"])
    def test_new_leaves_nest_under_search_and_gate(self, index_kind):
        clock = SimClock()
        rec = TraceRecorder(clock)
        cache = SemanticCache(_policies(), dim=DIM, capacity=64,
                              clock=clock, seed=0, index_kind=index_kind,
                              use_device=True, obs=rec)
        v = _bank(6, 8)
        for lo in (0, 4):
            cache.insert_batch(v[lo:lo + 4], ["a"] * 4,
                               [f"q{i}" for i in range(4)],
                               [f"r{i}" for i in range(4)])
            cache.lookup_batch(v[:4], ["a"] * 4)
        by_id = {sp.span_id: sp for sp in rec.spans}

        def parents(stage):
            return {by_id[sp.parent_id].stage for sp in rec.spans
                    if sp.stage == stage}
        assert parents("device_wait") == {"search"}
        assert parents("delta_flush") == {"search"}
        assert parents("clock_charge") == {"search", "gate"}
        flush = [sp for sp in rec.spans if sp.stage == "delta_flush"]
        assert len(flush) == 2                  # one per write-then-search
        assert flush[0].attrs["full"] == 1      # first use: full upload
        assert flush[0].attrs["bucket"] == 64
        assert flush[1].attrs["full"] == 0
        assert flush[1].attrs["rows"] >= 4
        assert flush[1].attrs["bucket"] >= flush[1].attrs["rows"]
        charges = [sp.attrs["ms"] for sp in rec.spans
                   if sp.stage == "clock_charge"]
        assert sorted(set(charges)) == [cache.insert_ms, cache.search_ms]
        assert check_span_accounting(rec) == []

    def test_flush_outside_a_search_is_its_own_root(self):
        from repro.core.hnsw import FlatIndex
        rec = TraceRecorder(SimClock())
        idx = FlatIndex(DIM, 32)
        idx.span = rec.span
        idx.add_batch(_bank(7, 3), np.zeros(3, np.int32))
        idx.device_tables()
        idx.device_tables()                     # nothing changed: no flush
        (sp,) = rec.spans
        assert sp.stage == "delta_flush" and sp.parent_id == NO_PARENT

    def test_flush_attrs_are_the_sync_counters(self):
        """``bucket`` and ``full`` come from the index's own sync
        counters: their sums are ``rows_synced`` and ``full_uploads``."""
        from repro.core.hnsw import FlatIndex, _bucket_batch
        rec = TraceRecorder(SimClock())
        idx = FlatIndex(DIM, 32)
        idx.span = rec.span
        for lo in (0, 3):
            idx.add_batch(_bank(7, 6)[lo:lo + 3], np.zeros(3, np.int32))
            idx.device_tables()
        first, second = [sp.attrs for sp in rec.spans]
        assert first == {"rows": 3, "bucket": 32, "full": 1}
        assert second == {"rows": 3, "bucket": _bucket_batch(3), "full": 0}
        assert first["bucket"] + second["bucket"] == \
            idx.sync_stats["rows_synced"]
        assert idx.sync_stats["full_uploads"] == 1

    def test_fault_run_on_the_device_path_closes_accounting(self):
        sched = FaultSchedule(shard_outages=[(2.0, 6.0, 0)],
                              store_get_failures=FaultSchedule.op_range(
                                  5, 2))
        res = _run(_sim_cfg(True, sched, index_kind="flat",
                            use_device=True), n=200)
        rec = res.trace
        stages = {sp.stage for sp in rec.spans}
        assert {"device_wait", "delta_flush", "clock_charge"} <= stages
        assert check_span_accounting(rec) == []
        assert coverage_fraction(rec) == pytest.approx(1.0)

    def test_replicated_and_migrating_device_caches_close_accounting(self):
        clock = SimClock()
        rec = TraceRecorder(clock)
        cache = ShardedSemanticCache(
            _policies(), dim=DIM, capacity=256, n_shards=2, clock=clock,
            seed=0, index_kind="flat", use_device=True,
            replication={"a": 2}, obs=rec)
        v = _bank(8, 24)
        cats = ["a"] * 12 + ["b"] * 12
        cache.insert_batch(v, cats, [f"q{i}" for i in range(24)],
                           [f"r{i}" for i in range(24)])
        cache.lookup_batch(v, cats)
        cache.migrate_category("b", 1 - cache.shard_of("b"))
        cache.lookup_batch(v, cats)
        stages = {sp.stage for sp in rec.spans}
        assert {"device_wait", "delta_flush", "migration"} <= stages
        assert check_span_accounting(rec) == []


# ------------------------------------------------------- engine queue wait
class TestEngineQueueWait:
    def _engine(self, clock, rec, max_batch):
        from repro.configs import get_config
        from repro.models import Model
        from repro.serving.engine import ServingEngine
        cfg = get_config("llama3_2_3b").reduced(n_layers=2, d_model=64,
                                                vocab_size=256)
        cache = SemanticCache(PolicyEngine(paper_policies()), capacity=256,
                              clock=clock, index_kind="flat", obs=rec)
        eng = ServingEngine(Model(cfg), None, cache, max_batch=max_batch,
                            prompt_len=16, max_new_tokens=4, obs=rec)
        eng._generate = lambda p, t: np.zeros((t.shape[0], 4), np.int32)
        return eng

    def test_one_queue_wait_per_served_request_from_submit(self):
        clock = SimClock()
        rec = TraceRecorder(clock)
        eng = self._engine(clock, rec, max_batch=2)
        submitted = {}
        for k in range(5):
            rid = eng.submit(f"question number {k} about lists",
                             "code_generation", np.arange(2, 10))
            submitted[rid] = clock.now()
            clock.advance(0.25)
        served = []
        while eng.queue:
            t_step, queued = clock.now(), len(eng.queue)
            out = eng.step()
            served += out
            ended = [sp for sp in rec.spans if sp.stage == "queue_wait"
                     and sp.t0 + sp.dur_ms / 1e3 == pytest.approx(t_step)]
            assert len(ended) == len(out) == min(2, queued)
            clock.advance(0.5)
        waits = [sp for sp in rec.spans if sp.stage == "queue_wait"]
        assert len(waits) == len(served) == 5
        assert sorted(sp.t0 for sp in waits) == sorted(submitted.values())
        assert all(sp.parent_id == NO_PARENT and sp.dur_ms >= 0
                   for sp in waits)
        assert {sp.category for sp in waits} == {"code_generation"}
        gen = [sp for sp in rec.spans if sp.stage == "model_generate"]
        assert gen and all(sp.attrs["bucket"] >= sp.attrs["batch"]
                           and sp.attrs["prompt_tokens"]
                           == 8 * sp.attrs["batch"] for sp in gen)
        assert check_span_accounting(rec) == []

    def test_recorder_clock_leaves_latencies_on_the_wall(self):
        """A SimClock recorder times ``queue_wait`` on its own clock; the
        responses' latencies and the engine's total stay on the wall
        clock, as with no recorder (a generate that takes 0.2 s of wall
        time and none of the SimClock's)."""
        import time

        def slow(p, t):
            time.sleep(0.2)
            return np.zeros((t.shape[0], 4), np.int32)
        for traced in (False, True):
            clock = SimClock()
            rec = TraceRecorder(clock) if traced else None
            eng = self._engine(clock, rec, max_batch=2)
            eng._generate = slow
            eng.submit("an uncached question", "code_generation",
                       np.arange(2, 10))
            (r,) = eng.step()
            assert not r.cached and r.latency_ms >= 200
            assert eng.stats.total_latency_ms == r.latency_ms
            if traced:
                (qw,) = [sp for sp in rec.spans if sp.stage == "queue_wait"]
                assert qw.dur_ms == 0.0      # the SimClock never moved

    def test_no_recorder_no_queue_wait(self):
        eng = self._engine(SimClock(), None, max_batch=2)
        eng.submit("a question", "code_generation", np.arange(2, 10))
        (r,) = eng.step()
        assert eng.obs is None and not r.cached
