"""Driver of an engine cell: ``ServingEngine`` with a model behind the
category-aware cache.

Set-up makes the weights on the device from the seed, fills the cache
with the mix's history, and warms the generate program at every miss
bucket the engine pads to. The window is open loop: each request is
``submit``-ted when it is due, and ``step`` serves the queue (up to
``max_batch`` a step); a request's latency runs from its due time to the
step that answered it. After the window the engine's state is freed and
the plain reference checks what the window produced.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from bench import harness as H
from bench.cache_ref import embed_text
from bench.drivers.cache import make_cache
from bench.traffic_gen import Mix, intent_prompt, intent_text


class AnnotatedCache:
    """The cache as the engine sees it, with host annotations around the
    calls the engine makes into it, and a record of each lookup (host
    times, batch, rows written since the last lookup: the delta flush it
    carries)."""

    def __init__(self, cache):
        self._cache = cache
        self.lookup_calls: list[tuple[float, float, int, int]] = []
        self.insert_calls = 0
        self._dirty: set[int] = set()

    def lookup_batch(self, emb, cats):
        t_lo = time.monotonic()
        with H.annotate("lookup_batch"):
            out = self._cache.lookup_batch(emb, cats)
        self.lookup_calls.append((t_lo, time.monotonic(), len(cats),
                                  len(self._dirty)))
        self._dirty.clear()
        return out

    def insert_batch(self, *a, **k):
        with H.annotate("insert_batch"):
            slots = self._cache.insert_batch(*a, **k)
        self.insert_calls += 1
        self._dirty.update(s for s in slots if s >= 0)
        return slots

    def __getattr__(self, name):
        return getattr(self._cache, name)


class AnnotatedEmbedder:
    def __init__(self, embedder):
        self._emb = embedder

    def embed_batch(self, texts):
        with H.annotate("embed_batch"):
            return self._emb.embed_batch(texts)


ROW_MATCH = 1e-5     # distinct feature-hash texts lie far below this


def reference_module(cell: H.Cell):
    return H.load_module(cell.config["reference"])


class Session:
    """The engine and the cache behind it, set up from the seed: weights
    made on the device, the history inserted, and the generate program
    warmed at every miss bucket the engine pads to."""

    def __init__(self, cell: H.Cell, seed: int, obs=None):
        import jax
        from repro.models.config import ArchConfig
        from repro.models.model import Model
        from repro.serving.engine import ServingEngine

        from bench.traffic_gen import jax_key

        cfg = self.cfg = cell.config
        self.cell, self.seed = cell, seed
        self.ref = reference_module(cell)
        arch = self.arch = cfg["arch"]
        params = jax.jit(lambda k: self.ref.make_weights(arch, k))(
            jax_key(seed, "weights"))
        jax.block_until_ready(params)
        cc = cfg["cache"]
        self.cache = make_cache(cfg, obs)
        eng = cfg["engine"]
        self.front = AnnotatedCache(self.cache)
        self.engine = ServingEngine(
            Model(ArchConfig(**arch)), params, self.front,
            max_batch=eng["max_batch"], prompt_len=eng["prompt_len"],
            max_new_tokens=eng["max_new_tokens"], obs=obs)
        self.engine.embedder = AnnotatedEmbedder(self.engine.embedder)
        self.mix = Mix(cell.traffic, seed, dim=cc["dim"])
        self.names = [c.name for c in self.mix.cats]
        # entries the benchmark caused: (category, text, response, step)
        self.entries: list[tuple[int, str, str, int]] = []
        self.step_no = 0
        self._history()
        self._warm()
        gc.collect()        # set-up's objects settle before the window

    def _history(self) -> None:
        """The mix's most recent distinct intents, as cached answers."""
        mix, names = self.mix, self.names
        h_cat, h_rank = mix.history(int(self.cell.traffic["history_requests"]))
        pairs = list(dict.fromkeys(zip(h_cat.tolist(), h_rank.tolist())))
        pairs = pairs[-int(self.cell.traffic["history_entries"]):]
        hc = np.array([p[0] for p in pairs])
        hi = mix.intent_of(hc, np.array([p[1] for p in pairs]))
        texts = [intent_text(mix, c, i) for c, i in zip(hc, hi)]
        resp = [f"hist-{names[c]}-{i}" for c, i in zip(hc, hi)]
        for s in range(0, len(texts), 4096):
            e = s + 4096
            self.cache.insert_batch(
                self.engine.embedder.embed_batch(texts[s:e]),
                [names[c] for c in hc[s:e]], texts[s:e], resp[s:e])
        self.entries += [(int(c), t, r, -1)
                         for c, t, r in zip(hc, texts, resp)]
        self._probe = (texts, hc)

    def _warm(self) -> None:
        """One all-miss step per generate bucket, then a lookup of every
        batch size a step can send (results are sliced to B)."""
        eng, names = self.engine, self.names
        mb, vocab = self.cfg["engine"]["max_batch"], self.arch["vocab_size"]
        rng = np.random.default_rng(7)
        for b in _buckets(mb):
            sent = {}
            for k in range(b):
                t = f"warm {b} {k} " + " ".join(
                    "".join(rng.choice(list("abcdefghij"), 6))
                    for _ in range(6))
                sent[eng.submit(t, names[k % len(names)],
                                rng.integers(1, vocab, 64))] = (
                    k % len(names), t)
            for r in eng.step():
                c, t = sent[r.req_id]
                self.entries.append((c, t, r.text, self.step_no))
            self.step_no += 1
        texts, hc = self._probe
        probe = eng.embedder.embed_batch(texts[:mb])
        for b in range(1, mb + 1):
            self.front.lookup_batch(probe[:b], [names[c] for c in hc[:b]])

    def serve_window(self, rate: float, seconds: float, tracer=None):
        """One open-loop window at ``rate``; returns the window, what the
        per-layer readers need, and the record the checks read."""
        eng, mix, names = self.engine, self.mix, self.names
        n = int(round(rate * seconds))
        cat, intent, rank = mix.requests(n)
        arr = mix.arrivals(n, seconds)
        vocab = self.arch["vocab_size"]
        rec = {"cat": cat,
               "text": [intent_text(mix, c, i) for c, i in zip(cat, intent)],
               "prompt": [intent_prompt(mix, c, i, r, vocab)
                          for c, i, r in zip(cat, intent, rank)],
               "step_of": np.full(n, -1, np.int64), "tokens": {},
               "out": {}}
        rid_of: dict[int, int] = {}
        gen_steps: list[tuple[float, float, list[int]]] = []

        def take(out, t_done, w):
            for r in out:
                g = rid_of[r.req_id]
                w.hit[g] = r.cached
                w.done[g] = t_done
                w.answered[g] = t_done <= w.t_end
                rec["step_of"][g] = self.step_no
                rec["out"][g] = r.text
                if not r.cached:
                    rec["tokens"][g] = r.tokens
                    self.entries.append((int(cat[g]), rec["text"][g],
                                         r.text, self.step_no))
            self.step_no += 1
            return [rid_of[r.req_id] for r in out if not r.cached]

        def submit(i):
            rid_of[eng.submit(rec["text"][i], names[cat[i]],
                              rec["prompt"][i])] = i

        census, lowered, gcp = H.Census(), H.Lowerings(), H.GcPauses()
        with census, lowered, gcp:
            t0 = time.monotonic() + 0.05
            w = H.Window(t0, seconds, t0 + arr, gc=gcp)
            c0 = census.compiles
            sync0 = self.cache.sync_stats["bytes_synced"]
            self.front.lookup_calls.clear()
            self.front.insert_calls = 0
            if tracer is not None:
                tracer.arm(t0, seconds)
            i = 0
            while True:
                now = time.monotonic()
                if now >= w.t_end:
                    break
                if tracer is not None:
                    tracer.poll(now)
                while i < n and w.due[i] <= now:
                    submit(i)
                    i += 1
                if eng.queue:
                    t_lo = time.monotonic()
                    with H.annotate("engine_step"):
                        out = eng.step()
                    miss = take(out, time.monotonic(), w)
                    if miss:
                        gen_steps.append((t_lo, time.monotonic(), miss))
                    continue
                H.wait_until(w.due[i] if i < n else w.t_end, w)
            w.compiles = census.compiles - c0
            w.lowered = list(lowered.names)
            if tracer is not None:
                tracer.finish()
        cc = self.cfg["cache"]
        layer = {"memory_peak_bytes": H.memory_peak_bytes(),
                 "counters": {"sync_bytes": self.cache.sync_stats[
                     "bytes_synced"] - sync0,
                     "insert_calls": self.front.insert_calls},
                 "lookup_calls": list(self.front.lookup_calls),
                 "n_rows": cc["capacity"], "dim": cc["dim"],
                 "emb_dtype": cc["emb_dtype"], "gen_steps": gen_steps,
                 "prompt_lens": {g: len(rec["prompt"][g]) for g in range(n)},
                 "arch": self.arch,
                 "max_new": self.cfg["engine"]["max_new_tokens"]}
        # Requests due in the window and unanswered: served untimed; they
        # count in the tails at the window's end.
        while i < n:
            submit(i)
            i += 1
        while eng.queue:
            take(eng.step(), np.inf, w)
        w.answered[w.done > w.t_end] = False
        return w, layer, rec


prepare = Session


def run(cell: H.Cell, seed: int, seconds: float, tracer=None, obs=None):
    """Set up, run the window, check. Returns (window, checks, layer)."""
    w, layer, checks, _ = _run(cell, seed, seconds, tracer, obs)
    return w, checks, layer


def readings(cell: H.Cell, seed: int, seconds: float) -> tuple[dict, dict]:
    """One run's checks, and the same checks with the control in the
    program's place: the reference with every matmul on fp8 operands, one
    step below the configuration's bf16, picks each token."""
    _, _, checks, control = _run(cell, seed, seconds, control=True)
    return checks, {**checks, **control}


def _run(cell, seed, seconds, tracer=None, obs=None, control=False):
    s = Session(cell, seed, obs)
    w, layer, rec = s.serve_window(cell.traffic["rate_per_s"], seconds,
                                   tracer)
    # free the program's state, then check with the plain reference
    dev_rows = device_rows(s.cache)
    ref, entries = s.ref, s.entries
    del s
    gc.collect()
    checks = correctness(cell, w, entries, rec, dev_rows)
    model, ctl = model_check(cell, ref, seed, rec["prompt"], rec["tokens"],
                             control)
    checks.update(model)
    return w, layer, checks, ctl


def _buckets(max_batch: int) -> list[int]:
    out, b = [], 8
    while b < max_batch:
        out.append(b)
        b *= 2
    return out + [max_batch]


def device_rows(cache) -> np.ndarray:
    """The device table's live rows (after the final delta flush)."""
    t = cache.index.device_tables()
    valid = np.asarray(t["valid"]).astype(bool)
    idx = np.nonzero(valid)[0]
    return np.asarray(t["emb"][idx])


def correctness(cell, w, entries, rec, dev_rows) -> dict:
    """The window's answers against the plain cache reference: each
    decision and each hit's text against an exact search over the entries
    the benchmark caused, and the device rows against those entries."""
    lim = cell.config["limits"]
    tol = lim["score_err_max"]
    cat, req_text, step_of = rec["cat"], rec["text"], rec["step_of"]
    texts_out = rec["out"]
    names = [c["name"] for c in cell.traffic["categories"]]
    tau = np.array([cell.config["policies"][nm]["threshold"]
                    for nm in names])
    memo: dict[str, np.ndarray] = {}

    def emb(t: str) -> np.ndarray:
        if t not in memo:
            memo[t] = embed_text(t, cell.config["cache"]["dim"])
        return memo[t]

    e_cat = np.array([e[0] for e in entries])
    e_born = np.array([e[3] for e in entries])
    e_emb = np.stack([emb(e[1]) for e in entries])
    e_resp = [e[2] for e in entries]
    served = np.nonzero(step_of >= 0)[0]
    mism = exc = bad_resp = 0
    for c in range(len(names)):
        rows = np.nonzero(e_cat == c)[0]
        reqs = served[cat[served] == c]
        if not reqs.size:
            continue
        sims = np.stack([emb(req_text[g]) for g in reqs]) @ e_emb[rows].T
        for j, g in enumerate(reqs):
            sc = np.where(e_born[rows] < step_of[g], sims[j], -np.inf)
            best = float(sc.max()) if sc.size else -np.inf
            ref_hit = best >= tau[c]
            if bool(w.hit[g]) != ref_hit:
                if abs(best - tau[c]) <= tol:
                    exc += 1
                else:
                    mism += 1
            if w.hit[g]:
                ok = {e_resp[rows[k]] for k in np.nonzero(
                    sc >= best - tol)[0]}
                bad_resp += texts_out[g] not in ok
    out = {"decision_mismatches": H.check_entry(mism, 0, mism == 0),
           "decisions_checked": H.check_entry(int(served.size), 1,
                                              served.size >= 1),
           "decisions_excused": {"value": exc},
           "hit_response_mismatches": H.check_entry(bad_resp, 0,
                                                    bad_resp == 0)}
    # device rows: each live row is one of the entries' embeddings (to
    # fp32 rounding of the feature hash: cosine within ROW_MATCH of 1),
    # and there are as many live rows as entries
    e32 = e_emb.astype(np.float32)
    far = 0
    for s in range(0, len(dev_rows), 2048):
        best = np.max(dev_rows[s:s + 2048] @ e32.T, axis=1)
        far += int(np.sum(best < 1.0 - ROW_MATCH))
    far += abs(len(dev_rows) - len(entries))
    out["device_row_mismatches"] = H.check_entry(far, 0, far == 0)
    out["device_rows_checked"] = H.check_entry(len(dev_rows), 1,
                                               len(dev_rows) >= 1)
    return out


def model_check(cell, ref, seed, req_prompt, tokens, control=False
                ) -> tuple[dict, dict | None]:
    """A seeded sample of the window's misses, the longest prompt among
    them, through the plain reference: by how far each served token's
    logit lies below the reference's best at its position, the widest
    gap and the mean over the served tokens. With ``control``, the same
    numbers of the tokens the fp8 control puts first, under the same
    limits."""
    from bench.traffic_gen import jax_key, seed_words
    eng = cell.config["engine"]
    lim = cell.config["limits"]
    done = sorted(tokens)
    if not done:
        return {"tokens_checked": H.check_entry(0, 1, False)}, None
    k = min(len(done), int(cell.traffic["check_requests"]))
    rng = np.random.default_rng(seed_words(seed, 3))
    longest = max(done, key=lambda g: len(req_prompt[g]))
    pick = [longest] + [g for g in rng.permutation(done).tolist()
                        if g != longest][:k - 1]
    P, new = eng["prompt_len"], eng["max_new_tokens"]
    seqs = np.zeros((len(pick), P + new - 1), np.int32)
    served = np.stack([tokens[g] for g in pick]).astype(np.int32)
    for j, g in enumerate(pick):
        p = req_prompt[g][:P]
        seqs[j, :len(p)] = p
        seqs[j, P:] = served[j, :-1]
    gaps = ref.served_token_gaps(cell.config["arch"],
                                 jax_key(seed, "weights"), seqs, served,
                                 first_pos=P - 1, control=control)

    def judged(g: np.ndarray) -> dict:
        out = {}
        for name, v in (("logit_gap_max", float(np.max(g))),
                        ("logit_gap_mean", float(np.mean(g)))):
            out[name] = (H.check_entry(v, lim[name], v <= lim[name])
                         if name in lim else {"value": v})
        return out

    out = {**judged(gaps["served"]),
           "tokens_checked": H.check_entry(int(served.size), 1,
                                           served.size >= 1)}
    return out, judged(gaps["control"]) if control else None


def layer_context(layer: dict, trace: dict | None, peaks: dict,
                  cell: H.Cell) -> dict:
    """Per-layer readers' inputs: the cache kernels' work, and the model
    FLOPs the window's generates required (real rows, real prompt
    tokens, routed experts only)."""
    ref = reference_module(cell)
    ctx = H.kernel_context(layer, trace, peaks)
    ctx["model_flops"] = sum(
        ref.request_flops(layer["arch"], layer["prompt_lens"][g],
                          layer["max_new"])
        for _, _, rows in layer["gen_steps"] for g in rows)
    return ctx
