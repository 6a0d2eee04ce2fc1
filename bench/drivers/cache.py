"""Driver of a cache-tier cell: the category-aware cache alone, in front of
remote model APIs (paper §5, §7.4).

Set-up fills a ``SemanticCache`` to the state a long history of the mix
leaves, then warms every program the window runs. The window is open
loop: every request that is due goes to ``lookup_batch`` (at most
``lookup_batch`` at a time), and each miss's write-back reaches
``insert_batch`` once its category's remote model would have answered
(Table 1's ``t_llm_ms``); write-backs due at once go in one batch. A
hit's latency runs from its due time to its answer, a miss's to its
verdict.
"""

from __future__ import annotations

import gc
import time
from collections import deque

import numpy as np

from bench import harness as H
from bench.cache_ref import Lookup, Mirror, decide, evictions
from bench.traffic_gen import Mix, fill_entries

FILL_CHUNK = 16384
FLUSH_BUCKETS = [8 << k for k in range(11)]     # 8 .. 8192 delta rows


class Stage:
    """The cache and the benchmark's record of it."""

    def __init__(self, cell: H.Cell, seed: int, obs=None):
        cfg = cell.config["cache"]
        self.cell = cell
        self.cache = make_cache(cell.config, obs)
        self.capacity = cfg["capacity"]
        self.mix = Mix(cell.traffic, seed, dim=cfg["dim"])
        self.names = [c.name for c in self.mix.cats]
        pol = cell.config["policies"]
        self.tau = np.array([pol[n]["threshold"] for n in self.names])
        self.ttl = np.array([pol[n]["ttl_s"] for n in self.names])
        self.delay = np.array([c.t_llm_ms / 1e3 for c in self.mix.cats])
        self.max_b = cell.config["lookup_batch"]
        self.mirror = None
        self.seq = 0
        self.dirty_rows: set[int] = set()     # slots written since a lookup
        self.evicted = 0                      # the cache's eviction count

    # -------------------------------------------------------------- calls
    def insert(self, emb: np.ndarray, cat: np.ndarray) -> np.ndarray:
        ids = self.mirror.reserve_ids(len(cat))
        names = [self.names[k] for k in cat]
        self.seq += 1
        t_lo = time.monotonic()
        with H.annotate("insert_batch"):
            slots = self.cache.insert_batch(
                emb, names, [f"req-{i}" for i in ids],
                [f"resp-{i}" for i in ids])
        t_hi = time.monotonic()
        slots = np.asarray(slots, np.int64)
        seen = self.mirror.record(ids, slots, emb, cat, self.seq, t_lo, t_hi)
        self.dirty_rows.update(slots[slots >= 0].tolist())
        # The cache hands an evicted entry's slot to a new one. Where its
        # eviction counters say it gave up more entries than that shows,
        # the victims are read from its occupancy.
        evicted = sum(s.quota_evictions + s.capacity_evictions
                      for s in map(self.cache.metrics.cat, self.names))
        if evicted - self.evicted > seen:
            self.mirror.reconcile(self.cache.slot_valid, self.seq)
        self.evicted = evicted
        return slots

    def serve_window(self, rate: float, seconds: float, tracer=None):
        return serve_window(self, rate, seconds, tracer)

    def lookup(self, emb: np.ndarray, cat: np.ndarray):
        self.seq += 1
        t_lo = time.monotonic()
        with H.annotate("lookup_batch"):
            res = self.cache.lookup_batch(emb, [self.names[k] for k in cat])
        t_hi = time.monotonic()
        self.mirror.hit(self.seq, [int(self.mirror.slot_entry[r.slot])
                                   for r in res if r.hit])
        return res, t_lo, t_hi


def make_cache(config: dict, obs=None):
    """The ``SemanticCache`` the configuration states, on a ``WallClock``
    as the live server builds it (the modeled search and insert charges
    are slept in the measured path), with the program's policy table
    checked against the configuration's."""
    from repro.core import policy as P
    from repro.core.cache import SemanticCache
    from repro.core.clock import WallClock
    cfg = config["cache"]
    if cfg["clock"] != "WallClock":
        raise H.SpecError(f"clock {cfg['clock']!r}: the benchmark serves "
                          f"on the wall clock")
    policies = P.PolicyEngine(getattr(P, cfg["policies"])())
    check_policies(policies, config["policies"])
    return SemanticCache(
        policies, dim=cfg["dim"], capacity=cfg["capacity"],
        index_kind=cfg["index_kind"], use_device=cfg["use_device"],
        emb_dtype=cfg["emb_dtype"], clock=WallClock(), obs=obs)


def check_policies(engine, stated: dict) -> None:
    """The program's policy table must be the one the configuration states
    (τ, TTL, quota, compliance): the reference decides by the latter."""
    for name, p in stated.items():
        c = engine.get(name)
        got = {"threshold": c.threshold, "ttl_s": c.ttl, "quota": c.quota,
               "allow_caching": c.allow_caching}
        if any(got[k] != p[k] for k in got):
            raise H.SpecError(f"policy {name}: program has {got}, the "
                              f"configuration states {p}")


def setup(st: Stage) -> None:
    """Fill to the history's state, then warm every shape the window uses:
    every lookup batch size up to the maximum and delta flushes of every
    row bucket up to 8192."""
    mix, cap = st.mix, st.capacity
    quota = {n: int(st.cell.config["policies"][n]["quota"] * cap)
             for n in st.names}
    cat, intent = fill_entries(mix, quota)
    emb = mix.embeddings(cat, intent, "fill")
    warm_rows = sum(FLUSH_BUCKETS)
    st.mirror = Mirror(cap, mix.dim, reserve=len(cat) + 65536)
    bulk = max(0, len(cat) - warm_rows)
    for s in range(0, bulk, FILL_CHUNK):
        e = min(bulk, s + FILL_CHUNK)
        st.insert(emb[s:e], cat[s:e])
    rng = mix.work_rng("warm")
    q_cat, q_rank = mix.draw_ranks(st.max_b, rng)
    q = mix.embeddings(q_cat, mix.intent_of(q_cat, q_rank), "warm")
    # every batch size the window can send (results are sliced to B), and
    # after each flush bucket's insert a lookup to carry the flush
    s, sizes = bulk, list(range(1, st.max_b + 1))
    st.lookup(q[:1], q_cat[:1])         # the first search uploads the table
    for k, b in enumerate(FLUSH_BUCKETS):
        e = min(len(cat), s + b)
        if e > s:
            st.insert(emb[s:e], cat[s:e])
        s = e
        bq = sizes.pop() if sizes else st.max_b
        st.lookup(q[:bq], q_cat[:bq])
    if s < len(cat):
        st.insert(emb[s:], cat[s:])
    for bq in sizes:
        st.lookup(q[:bq], q_cat[:bq])
    st.dirty_rows.clear()
    st.fill_rows = len(cat)
    st.setup_seq = st.seq
    # The fill's million host objects settle into the oldest generation
    # now, as in a server that has run for a while, not in the window.
    gc.collect()


def prepare(cell: H.Cell, seed: int, obs=None) -> Stage:
    st = Stage(cell, seed, obs)
    setup(st)
    return st


def run(cell: H.Cell, seed: int, seconds: float, tracer=None, obs=None):
    """Set up, run the window, check. Returns (window, checks, layer)."""
    st = prepare(cell, seed, obs)
    w, layer, lookups, hits_seen = serve_window(
        st, cell.traffic["rate_per_s"], seconds, tracer)
    return w, correctness(st, lookups, hits_seen), layer


def readings(cell: H.Cell, seed: int, seconds: float) -> tuple[dict, dict]:
    """One run's checks, and the same checks with the control in the
    program's place: the reference scanning at three bf16 passes
    (``control_lookups``) answers the checked lookups."""
    st = prepare(cell, seed)
    _, _, lookups, hits_seen = serve_window(
        st, cell.traffic["rate_per_s"], seconds)
    checks = correctness(st, lookups, hits_seen)
    return checks, {**checks, **search_checks(
        st, control_lookups(st, lookups))}


def serve_window(st: Stage, rate: float, seconds: float, tracer=None):
    """One open-loop window at ``rate`` requests per second. Returns the
    window, what the per-layer readers need, the checked lookups and
    every hit's (entry, response)."""
    cell, mix = st.cell, st.mix
    n = int(round(rate * seconds))
    cat, intent, _ = mix.requests(n)
    emb = mix.embeddings(cat, intent, "window")
    arr = mix.arrivals(n, seconds)
    sample = set(mix.run_rng.choice(
        n, size=min(n, cell.traffic["check_lookups"]), replace=False)
        .tolist())
    hits_seen: list[tuple[int, str]] = []     # (entry, response) per hit
    lookups: list[Lookup] = []
    layer = {"lookup_calls": [], "insert_calls": 0}
    wb = {d: deque() for d in set(st.delay.tolist())}   # FIFO per delay

    def serve(i: int, j: int, w: H.Window | None):
        dirty = len(st.dirty_rows)
        st.dirty_rows.clear()
        res, t_lo, t_hi = st.lookup(emb[i:j], cat[i:j])
        layer["lookup_calls"].append((t_lo, t_hi, j - i, dirty))
        for k, r in enumerate(res):
            g = i + k
            if w is not None:
                w.hit[g] = r.hit
                w.done[g] = t_hi
                w.answered[g] = t_hi <= w.t_end
            ent = int(st.mirror.slot_entry[r.slot]) if r.hit else -1
            if r.hit:
                hits_seen.append((ent, r.response))
            else:
                d = st.delay[cat[g]]
                wb[d].append((t_hi + d, g))
            if g in sample:
                lookups.append(Lookup(
                    st.seq, t_lo, t_hi, emb[g], int(cat[g]),
                    float(st.tau[cat[g]]), float(st.ttl[cat[g]]),
                    bool(r.hit), ent, float(r.score)))

    census, lowered, gcp = H.Census(), H.Lowerings(), H.GcPauses()
    with census, lowered, gcp:
        t0 = time.monotonic() + 0.05
        w = H.Window(t0, seconds, t0 + arr, gc=gcp)
        c0 = census.compiles
        sync0 = st.cache.sync_stats["bytes_synced"]
        if tracer is not None:
            tracer.arm(t0, seconds)
        i = 0
        while True:
            now = time.monotonic()
            if now >= w.t_end:
                break
            if tracer is not None:
                tracer.poll(now)
            due_wb = []
            for q in wb.values():
                while q and q[0][0] <= now:
                    due_wb.append(q.popleft()[1])
            if due_wb:
                g = np.asarray(due_wb)
                st.insert(emb[g], cat[g])
                layer["insert_calls"] += 1
            # one insert batch, then one lookup batch: neither starves
            now = time.monotonic()
            j = i
            while j < n and j - i < st.max_b and w.due[j] <= now:
                j += 1
            if j > i:
                serve(i, j, w)
                i = j
                continue
            if due_wb:
                continue
            nxt = [w.t_end]
            if i < n:
                nxt.append(w.due[i])
            nxt += [q[0][0] for q in wb.values() if q]
            H.wait_until(min(nxt), w)
        w.compiles = census.compiles - c0
        w.lowered = list(lowered.names)
        if tracer is not None:
            tracer.finish()
    layer["memory_peak_bytes"] = H.memory_peak_bytes()
    layer["counters"] = {
        "sync_bytes": st.cache.sync_stats["bytes_synced"] - sync0,
        "insert_calls": layer["insert_calls"]}
    # Requests due in the window and not yet answered: learn their class
    # (untimed); they count in the tails at the window's end.
    while i < n:
        j = min(n, i + st.max_b)
        serve(i, j, w)
        w.answered[i:j] = False
        i = j
    layer.update(n_rows=st.capacity, dim=st.mix.dim,
                 emb_dtype=cell.config["cache"]["emb_dtype"],
                 fill_rows=st.fill_rows)
    return w, layer, lookups, hits_seen


def correctness(st: Stage, lookups: list[Lookup],
                hits_seen: list[tuple[int, str]]) -> dict:
    """Compare what the timed path produced with the plain reference."""
    out = {}
    # 1. each hit's response is the one inserted for that slot's entry
    bad = sum(1 for ent, resp in hits_seen if resp != f"resp-{ent}")
    out["hit_response_mismatches"] = H.check_entry(
        bad, 0, bad == 0)
    out["hits_checked"] = H.check_entry(len(hits_seen), 1,
                                        len(hits_seen) >= 1)
    # 2. the delta-flushed device rows equal the rows inserted
    out.update(device_rows(st))
    # 3. the evictions against the quota rule
    pol = st.cell.config["policies"]
    ev = evictions(
        st.mirror,
        np.array([int(pol[n]["quota"] * st.capacity) for n in st.names]),
        np.array([pol[n]["allow_caching"] and pol[n]["quota"] > 0
                  for n in st.names]))
    bad = ev["wrong_victims"] + ev["wrong_counts"] + ev["unstored"]
    out["eviction_mismatches"] = H.check_entry(bad, 0, bad == 0)
    out["evictions_checked"] = H.check_entry(ev["victims"], 1,
                                             ev["victims"] >= 1)
    # 4. the device search's decisions against the exact host reference
    out.update(search_checks(st, lookups))
    return out


def search_checks(st: Stage, lookups: list[Lookup]) -> dict:
    """The checked lookups' decisions and scores against exact ones."""
    lim = st.cell.config["limits"]
    d = decide(st.mirror, lookups, lim["score_err_max"])
    out = {k: H.check_entry(d[k], lim[k], d[k] <= lim[k])
           for k in ("score_err_max", "score_err_rms")}
    out["decision_mismatches"] = H.check_entry(
        d["decision_mismatches"], 0, d["decision_mismatches"] == 0)
    out["decisions_checked"] = H.check_entry(
        d["decisions_checked"], 1, d["decisions_checked"] >= 1)
    out["decisions_excused"] = {"value": d["decisions_excused"]}
    return out


def device_rows(st: Stage, n_fill: int = 512) -> dict:
    """Device rows of every slot written after set-up, plus a seeded sample
    of fill slots, against the embeddings the benchmark inserted; and the
    device's occupancy, slot by slot, against the entries resident."""
    import jax.numpy as jnp
    m = st.mirror
    live = np.nonzero(m.slot_entry >= 0)[0]
    late = live[m.born[m.slot_entry[live]] > st.setup_seq]
    rng = np.random.default_rng(0)
    pick = np.unique(np.concatenate(
        [late, rng.choice(live, size=min(n_fill, live.size),
                          replace=False)]))
    t = st.cache.index.device_tables()
    dev = np.asarray(t["emb"][jnp.asarray(pick)])
    valid = np.asarray(t["valid"][jnp.asarray(pick)])
    want = m.emb[m.slot_entry[pick]]
    bad = int(np.sum(np.any(dev != want, axis=1) | ~valid.astype(bool)))
    bad += int(np.sum(np.asarray(t["valid"]).astype(bool)
                      != (m.slot_entry >= 0)))
    return {"device_row_mismatches": H.check_entry(bad, 0, bad == 0),
            "device_rows_checked": H.check_entry(int(pick.size), 1,
                                                 pick.size >= 1)}


def layer_context(layer: dict, trace: dict | None, peaks: dict,
                  cell: H.Cell) -> dict:
    return H.kernel_context(layer, trace, peaks)


def control_lookups(st: Stage, lookups: list[Lookup]) -> list[Lookup]:
    """The checked lookups answered by the control: an exact scan over the
    same resident entries whose dot products take three bf16 passes
    (``Precision.HIGH``: hi·hi + hi·lo + lo·hi, fp32 accumulation), the
    step below the ``Precision.HIGHEST`` the configuration's fp32 search
    states."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def high(q, e):
        def split(x):
            # hi: x rounded to bf16 (to nearest, ties to even) in integer
            # arithmetic, which the compiler cannot fold away; lo: the
            # rest, in bf16
            bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
            bits = bits + jnp.uint32(0x7FFF) + ((bits >> 16) & 1)
            hi = jax.lax.bitcast_convert_type(
                bits & jnp.uint32(0xFFFF0000), jnp.float32)
            return hi.astype(jnp.bfloat16), (x - hi).astype(jnp.bfloat16)
        (qh, ql), (eh, el) = split(q), split(e)
        mm = lambda a, b: jnp.matmul(a, b.T,
                                     preferred_element_type=jnp.float32)
        return mm(qh, eh) + mm(qh, el) + mm(ql, eh)

    m = st.mirror
    out = []
    by_cat: dict[int, list[Lookup]] = {}
    for lk in lookups:
        by_cat.setdefault(lk.cat, []).append(lk)
    for c, group in by_cat.items():
        rows = np.nonzero(m.cat[:m.n] == c)[0]
        e = jnp.asarray(m.emb[rows])
        for s in range(0, len(group), 64):
            part = group[s:s + 64]
            sc = np.asarray(high(jnp.asarray(np.stack(
                [lk.query for lk in part])), e))
            for j, lk in enumerate(part):
                alive = (m.born[rows] < lk.seq) & (m.died[rows] > lk.seq)
                row = np.where(alive, sc[j], -np.inf)
                k = int(np.argmax(row))
                score = float(row[k])
                ent = int(rows[k]) if np.isfinite(score) else -1
                hit = ent >= 0 and score >= lk.tau and \
                    lk.t_lo - m.t_hi[ent] <= lk.ttl
                out.append(Lookup(lk.seq, lk.t_lo, lk.t_hi, lk.query, c,
                                  lk.tau, lk.ttl, hit, ent if hit else -1,
                                  score))
    return out
