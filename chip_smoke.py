"""On-chip smoke of the cached serving path, on one TPU chip.

    python3 chip_smoke.py [--seed 0]

One process; every input (cache contents, queries, model weights) is
generated from ``--seed``. The phases run in order, and any failed check
raises, so the exit code is non-zero and the verdict line is never
printed:

  device  JAX's platform, device kind and device count. Anything but a
          TPU exits non-zero at once (there is no CPU fallback).
  flat    A 2^20-row fp32 ``SemanticCache(index_kind="flat",
          use_device=True)`` (1.6 GB resident) filled through
          ``insert_batch`` with the Table-1 mix, then searched on the
          device by the flat_topk kernel. Its ids and scores are checked
          against the exact host scan (``FlatIndex.search_host``) and the
          hits of ``lookup_batch`` against the inserted responses.
  hnsw    A 131,072-entry int8 HNSW index (``HNSWIndex.bulk_build``)
          searched with the fused frontier-hop kernel; its ids must equal
          the reference hop's on the chip, and its recall@1 against the
          exact host oracle is reported.
  served  ``ServingEngine`` with granite-moe-3b-a800m at its published
          width and depth (random weights) behind the flat phase's cache:
          misses go to the model and come back through ``insert_batch``
          and a Pallas delta flush, repeats are served as hits with the
          inserted text. Then the 2-shard front door, once, at a small
          capacity.

Each phase prints one ``[phase] {...}`` line: the kernels it traced and
whether they were compiled (never interpreted), compile seconds and
persistent-cache hits, the compile-cache directory, the device's
``peak_bytes_in_use``, and what it served. The last line of standard
output is the verdict, ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

FLAT_ROWS = 1 << 20          # the paper's §7.4 budget: 2^20 x 384 fp32
HNSW_ROWS = 1 << 17          # bulk-built int8 graph
N_QUERIES = 64               # queries per search check
SCORE_TOL = 1e-5             # |device - host| on unit-vector cosines


class Census:
    """What ran: compile seconds and persistent-cache hits (JAX's
    monitoring events), every Pallas kernel traced with its ``interpret``
    flag, and calls of the Pallas delta-flush kernel. A context manager:
    the kernel hooks are removed on exit."""

    def __init__(self):
        self.compile_s = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self.traced: list[tuple[str, bool]] = []   # (kernel, interpreted)
        self.pallas_flushes = 0

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration
            self.compiles += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def __enter__(self):
        import jax
        from jax.experimental import pallas as pl

        from repro.kernels import ops

        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        self._real = (pl.pallas_call, ops._su.scatter_rows)
        real_call, real_scatter = self._real

        def pallas_call(kernel, *a, **kw):
            fn = getattr(kernel, "func", kernel)
            self.traced.append((fn.__name__, bool(kw.get("interpret"))))
            return real_call(kernel, *a, **kw)

        def scatter_rows(*a, **kw):
            self.pallas_flushes += 1
            return real_scatter(*a, **kw)

        pl.pallas_call = pallas_call        # kernels resolve it per call
        ops._su.scatter_rows = scatter_rows
        return self

    def __exit__(self, *_):
        import jax
        from jax.experimental import pallas as pl

        from repro.kernels import ops
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)
        pl.pallas_call, ops._su.scatter_rows = self._real
        return False

    def snapshot(self) -> tuple:
        return (self.compile_s, self.compiles, self.cache_hits,
                len(self.traced))


class Phase:
    """Times one phase and prints its line; on the chip, every kernel the
    phase traced must have been compiled, not interpreted."""

    def __init__(self, name: str, census: Census, device, cache_dir: str,
                 on_chip: bool = True):
        self.name, self.census, self.device = name, census, device
        self.cache_dir, self.on_chip = cache_dir, on_chip

    def __enter__(self):
        self.t0 = time.monotonic()
        self.before = self.census.snapshot()
        self.fields: dict = {}
        return self

    def __exit__(self, exc_type, *_):
        if exc_type is not None:
            return False
        s0, n0, h0, k0 = self.before
        c = self.census
        kernels = sorted({f"{k}:{'interpreted' if v else 'compiled'}"
                          for k, v in c.traced[k0:]})
        if self.on_chip:
            assert all(k.endswith(":compiled") for k in kernels), kernels
        stats = self.device.memory_stats() or {}
        line = {"seconds": round(time.monotonic() - self.t0, 3),
                "kernels": kernels,
                "compile_s": round(c.compile_s - s0, 3),
                "compiles": c.compiles - n0,
                "compile_cache_hits": c.cache_hits - h0,
                "compile_cache_dir": self.cache_dir,
                "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
                **self.fields}
        print(f"[{self.name}] {json.dumps(line)}", flush=True)
        return False


def _texts(cats, intents, start: int):
    """Request and unique response texts for inserted rows."""
    req = [f"{c}:intent{int(i)}" for c, i in zip(cats, intents)]
    resp = [f"resp:{c}:{int(i)}:row{start + j}"
            for j, (c, i) in enumerate(zip(cats, intents))]
    return req, resp


def phase_flat(ph: Phase, policies, gen, *, rows: int, n_queries: int):
    """Fill a flat fp32 device-resident cache; check the device search
    against the exact host scan and the cache's hits against the inserted
    responses. Returns the filled cache."""
    import numpy as np

    from repro.core.cache import SemanticCache
    from repro.core.clock import SimClock

    cache = SemanticCache(policies, dim=384, capacity=rows, clock=SimClock(),
                          index_kind="flat", use_device=True)
    # 3/4 of the rows: under the Table-1 traffic shares no category then
    # reaches its quota (api_documentation: 0.25 of traffic, 0.20 quota),
    # so the fill measures inserts, not quota evictions.
    fill = 3 * rows // 4
    emb, cats, intents = gen.sample_entries(fill)
    response_of_slot = {}
    chunk = 16384
    for s in range(0, fill, chunk):
        e = min(fill, s + chunk)
        req, resp = _texts(cats[s:e], intents[s:e], s)
        slots = cache.insert_batch(emb[s:e], cats[s:e], req, resp)
        response_of_slot.update(zip(slots, resp))
    assert len(cache) == fill, (len(cache), fill)

    q, qcats, _ = gen.sample_entries(n_queries)
    cid = np.array([policies.category_id(c) for c in qcats], np.int32)
    taus = np.array([policies.effective(c).threshold for c in qcats],
                    np.float32)
    d_idx, d_score = (np.asarray(a) for a in
                      cache.index.search_batch(q, taus, categories=cid))
    h_idx, h_score = cache.index.search_host(q, taus, categories=cid)

    # Exact same-category sims on the host: the top-2 gap excuses an id
    # that differs only inside a near-tie, and a score within SCORE_TOL
    # of τ excuses a hit/miss flip. Nothing else may differ.
    idx = cache.index
    sims = q @ idx.emb[:idx._n].T
    ok = idx.valid[None, :idx._n] & (idx.category[None, :idx._n]
                                     == cid[:, None])
    sims = np.where(ok, sims, -np.inf)
    top2 = -np.sort(-sims, axis=1)[:, :2]
    finite = np.isfinite(h_score)
    assert np.array_equal(np.isfinite(d_score), finite)
    err = float(np.max(np.abs(d_score[finite] - h_score[finite]),
                       initial=0.0))
    assert err <= SCORE_TOL, err
    tie = (top2[:, 0] - top2[:, 1]) <= 2 * SCORE_TOL
    near_tau = np.abs(h_score - taus) <= SCORE_TOL
    differ = d_idx != h_idx
    assert not np.any(differ & ~tie & ~near_tau), (d_idx, h_idx)

    res = cache.lookup_batch(q, qcats)
    hits = [r for r in res if r.hit]
    for r in hits:
        assert r.response == response_of_slot[r.slot], (r.slot, r.response)
    assert hits, "no query of the Table-1 mix hit the filled cache"
    ph.fields.update(
        rows=rows, live_entries=len(cache), queries=n_queries,
        max_abs_score_err=err, score_tol=SCORE_TOL,
        ids_equal=int(np.sum(~differ)), ids_excused_tie_or_tau=int(
            np.sum(differ)), served=len(res), hits=len(hits),
        misses=len(res) - len(hits),
        resident_table_bytes=int(idx.emb.nbytes))
    return cache


def phase_hnsw(ph: Phase, policies, gen, *, rows: int, n_queries: int,
               on_chip: bool, seed: int):
    """int8 HNSW: the fused hop must return the reference hop's ids;
    recall@1 of the full search against the exact host oracle."""
    import numpy as np

    from repro.core.hnsw import HNSWIndex, HNSWParams

    vecs, cats, _ = gen.sample_entries(rows)
    cid = np.array([policies.category_id(c) for c in cats], np.int32)
    index = HNSWIndex.bulk_build(vecs, capacity=rows, seed=seed,
                                 params=HNSWParams(emb_dtype="int8"),
                                 categories=cid)
    impl = index._resolve_hop_impl()
    assert impl == ("fused" if on_chip else "reference"), impl

    q, qcats, _ = gen.sample_entries(n_queries)
    qcid = np.array([policies.category_id(c) for c in qcats], np.int32)
    taus = np.array([policies.effective(c).threshold for c in qcats],
                    np.float32)
    never = np.full(n_queries, 2.0, np.float32)     # τ no cosine reaches

    def run(hop_impl):
        index.p.hop_impl = hop_impl
        idx, _ = index.search_batch(q, taus, categories=qcid)
        _, _, _, cand = index.search_classified(q, never, categories=qcid)
        return np.asarray(idx), np.asarray(cand)

    f_idx, f_cand = run("fused")
    r_idx, r_cand = run("reference")
    index.p.hop_impl = None
    assert np.array_equal(f_idx, r_idx), (f_idx, r_idx)
    assert np.array_equal(f_cand, r_cand), (f_cand, r_cand)

    sims = q @ vecs.T
    sims = np.where(cid[None, :] == qcid[:, None], sims, -np.inf)
    exact = np.argmax(sims, axis=1)
    recall = float(np.mean(f_cand == exact))
    hits = int(np.sum(f_idx >= 0))
    ph.fields.update(rows=rows, emb_dtype="int8", hop_impl=impl,
                     queries=n_queries, fused_ids_equal_reference=True,
                     recall_at_1=recall,
                     no_same_category_candidate=int(np.sum(f_cand < 0)),
                     served=n_queries, hits=hits, misses=n_queries - hits)


def phase_served(ph: Phase, census: Census, policies, cache, cfg, *,
                 seed: int, shard_capacity: int, on_chip: bool):
    """granite-moe behind the filled cache: misses to the model and back
    through insert_batch + the Pallas delta flush; repeats hit with the
    inserted text. Then the 2-shard front door."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.clock import SimClock
    from repro.core.shard import ShardedSemanticCache
    from repro.models.model import Model
    from repro.serving.engine import ServingEngine

    model = Model(cfg)
    params = jax.jit(model.init_params)(jax.random.key(seed))
    rng = np.random.default_rng(seed)
    prompt_len, new = 32, 8
    cats = ["code_generation", "api_documentation", "conversational_chat",
            "legal_queries"]

    letters = np.array(list("abcdefghijklmnopqrstuvwxyz"))

    def requests(n):
        """n requests with random-word texts: no two alike enough to hit
        each other, so only an exact repeat is a hit."""
        return [(" ".join("".join(rng.choice(letters, 7)) for _ in range(10)),
                 cats[i % len(cats)],
                 rng.integers(2, cfg.vocab_size, prompt_len))
                for i in range(n)]

    def serve_round(engine, first, repeat):
        out = {}
        for text, cat, toks in first + repeat:
            rid = engine.submit(text, cat, toks)
            out[rid] = text
        got = {r.req_id: r for r in engine.drain()}
        return {out[rid]: r for rid, r in got.items()}

    flushes0 = census.pallas_flushes
    syncs0 = dict(cache.index.sync_stats)
    engine = ServingEngine(model, params, cache, max_batch=8,
                           prompt_len=prompt_len, max_new_tokens=new)
    first = requests(24)
    r1 = serve_round(engine, first, [])
    assert not any(r.cached for r in r1.values())
    model_text = {t: r.text for t, r in r1.items()}
    # 16 repeats of round 1 (hits) and 8 new requests (misses)
    r2 = serve_round(engine, requests(8), first[:16])
    hits = [t for t, r in r2.items() if r.cached]
    assert sorted(hits) == sorted(t for t, _, _ in first[:16]), hits
    for t in hits:
        assert r2[t].text == model_text[t], (t, r2[t].text)
    for r in list(r1.values()) + list(r2.values()):
        if not r.cached:
            assert r.tokens.shape == (new,)
            assert np.all((r.tokens >= 0) & (r.tokens < cfg.vocab_size))
    flushes = census.pallas_flushes - flushes0
    deltas = cache.index.sync_stats["delta_updates"] - syncs0["delta_updates"]
    assert deltas >= 1 and (flushes >= 1 or not on_chip), (flushes, deltas)
    # The flushed rows on the device equal the host rows they mirror.
    slots = np.array([r.slot for r in cache.lookup_batch(
        engine.embedder.embed_batch([t for t, _, _ in first[:8]]),
        [c for _, c, _ in first[:8]])], np.int64)
    dev_rows = np.asarray(cache.index.device_tables()["emb"][slots])
    assert np.array_equal(dev_rows, cache.index.emb[slots])

    # The model's own outputs: last-token logits of one prompt batch.
    toks = jnp.asarray(np.stack([t for _, _, t in first[:8]]))
    logits = jax.jit(lambda p, t: model.prefill(
        p, {"tokens": t}, prompt_len + new)[0])(params, toks)
    logits = np.asarray(logits[:, :cfg.vocab_size], np.float32)
    assert logits.shape == (8, cfg.vocab_size)
    assert np.all(np.isfinite(logits))

    st = engine.stats
    single = dict(served=st.served, hits=st.cache_hits,
                  misses=st.served - st.cache_hits,
                  pallas_delta_flushes=flushes, delta_updates=deltas)

    sharded = ShardedSemanticCache(policies, n_shards=2,
                                   capacity=shard_capacity, clock=SimClock(),
                                   index_kind="flat", use_device=True)
    eng2 = ServingEngine(model, params, sharded, max_batch=8,
                         prompt_len=prompt_len, max_new_tokens=new)
    first2 = requests(8)
    serve_round(eng2, first2, [])
    r4 = serve_round(eng2, [], first2)
    assert all(r.cached for r in r4.values())
    st2 = eng2.stats
    ph.fields.update(
        arch=cfg.name, n_layers=cfg.n_layers, d_model=cfg.d_model,
        n_experts=cfg.n_experts, top_k=cfg.moe_top_k,
        vocab=cfg.vocab_size, param_bytes=int(sum(
            x.nbytes for x in jax.tree.leaves(params))),
        logits_finite=True, single_cache=single,
        sharded=dict(shards=2, capacity=shard_capacity, served=st2.served,
                     hits=st2.cache_hits,
                     misses=st2.served - st2.cache_hits),
        served=st.served + st2.served,
        hits=st.cache_hits + st2.cache_hits,
        misses=(st.served - st.cache_hits) + (st2.served - st2.cache_hits))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"[device] {json.dumps(device)}", flush=True)
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found platform {dev.platform!r} "
              f"({dev.device_kind}), not a TPU; there is no CPU fallback",
              file=sys.stderr)
        return 2

    from repro.configs import get_config
    from repro.core.policy import PolicyEngine, paper_policies
    from repro.core.workload import TABLE1_WORKLOAD, WorkloadGenerator
    from repro.kernels import ops
    from repro.launch.compile_cache import configure_compile_cache

    cache_dir = configure_compile_cache()
    assert not ops._on_cpu()
    policies = PolicyEngine(paper_policies())
    gen = WorkloadGenerator(TABLE1_WORKLOAD, seed=args.seed)
    with Census() as census:
        with Phase("flat", census, dev, cache_dir) as ph:
            cache = phase_flat(ph, policies, gen, rows=FLAT_ROWS,
                               n_queries=N_QUERIES)
        with Phase("hnsw", census, dev, cache_dir) as ph:
            phase_hnsw(ph, policies, gen, rows=HNSW_ROWS,
                       n_queries=N_QUERIES, on_chip=True, seed=args.seed)
        with Phase("served", census, dev, cache_dir) as ph:
            phase_served(ph, census, policies, cache,
                         get_config("granite_moe_3b_a800m"), seed=args.seed,
                         shard_capacity=4096, on_chip=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
