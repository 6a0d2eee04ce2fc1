"""Live batched serving engine: semantic cache in front of a real JAX model.

The end-to-end path (examples/serve_e2e.py):

    submit(Request) → queue → step():
        embed queries (feature-hash, 384-d)
        cache.lookup_batch with per-request categories  (Algorithm 1)
          — the per-request category vector rides into the index search
            (§5.3), so mixed-category batches resolve to same-category
            matches with no cross-category false misses
        hits  → respond from cache (no model tokens burned)
        misses → batch → prefill → greedy decode loop → respond +
                 ONE cache.insert_batch for the whole batch's write-backs
                 (one store pass, one index delta flush — the device
                 tables sync O(batch) bytes, not O(capacity))

A lookup that finds both hits and misses returns its hits at once and
leaves its misses, looked up, at the head of the queue: the next step()
generates them and writes them back, with no lookup of its own. So a hit
waits for no generate of its own step, and every lookup and insert keeps
its order. An all-miss lookup generates in the same step.

Latency/queue-depth observations feed the ``AdaptiveController`` so cache
policies relax under load (§7.5) — on a real deployment this is the same
code path, just with a bigger mesh under ``Dist``.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.cache import SemanticCache
from repro.core.embedding import FeatureHashEmbedder
from repro.core.hnsw import _bucket_batch
from repro.core.policy import AdaptiveController, LoadSignal
from repro.core.shard import ShardedSemanticCache
from repro.distributed.fault import StepWatchdog
from repro.models.model import Model
from repro.obs import NULL_SPAN


@dataclass
class Request:
    req_id: int
    text: str
    category: str
    prompt_tokens: np.ndarray           # (S,) int32
    max_new_tokens: int = 16
    arrival: float = 0.0                # submit, time.monotonic
    traced_at: float = 0.0              # submit, on the recorder's clock
    emb: np.ndarray | None = None       # set when its lookup missed and
                                        # its generate is still to come


@dataclass
class Response:
    req_id: int
    text: str
    tokens: np.ndarray | None
    cached: bool
    latency_ms: float
    category: str
    reason: str = ""


@dataclass
class EngineStats:
    served: int = 0
    cache_hits: int = 0
    model_tokens: int = 0
    total_latency_ms: float = 0.0
    # per-reason serve counts ("hit", "hit_l1", "model", ...) — with the
    # category-masked index there is no "category_mismatch" miss anymore;
    # cross-category traffic shows up as genuine "no_match"/"model".
    reasons: dict = field(default_factory=dict)
    # device-search data-plane counters (from cache.last_lookup_stats):
    # beam hops run and embedding rows gathered across all lookups — the
    # deterministic cost signal the lookup benchmark gates on.
    search_hops: int = 0
    rows_gathered: int = 0
    # steps the watchdog flagged as stragglers (wall time > factor × the
    # trailing-median step time) — the serving-side liveness signal.
    straggler_steps: int = 0
    # hits answered before their lookup's misses were generated, and the
    # generates so deferred to the next step()
    hits_before_generate: int = 0
    deferred_generates: int = 0

    @property
    def hit_rate(self) -> float:
        return self.cache_hits / self.served if self.served else 0.0

    def count_reason(self, reason: str) -> None:
        self.reasons[reason] = self.reasons.get(reason, 0) + 1


class ServingEngine:
    """Queue → embed → cache lookup → model on misses → batched
    write-back. ``cache`` is a ``SemanticCache`` or, for multi-shard
    residency, a ``ShardedSemanticCache`` — the fan-out/merge happens
    behind the same lookup_batch/insert_batch API, and
    ``last_lookup_stats`` arrives pre-aggregated across shards so the
    hop/row counters below stay topology-blind."""

    def __init__(self, model: Model, params,
                 cache: SemanticCache | ShardedSemanticCache,
                 *, max_batch: int = 8, prompt_len: int = 64,
                 max_new_tokens: int = 16,
                 controller: AdaptiveController | None = None,
                 model_name: str = "default",
                 watchdog: StepWatchdog | None = None,
                 obs=None):
        self.model = model
        self.params = params
        self.cache = cache
        # Optional TraceRecorder (repro.obs). Share ONE recorder (and
        # one WallClock) with the cache — launch/serve.py does this —
        # so cache stage spans nest under the engine_step root. Wall
        # time is not exhaustively charged, so span accounting reports
        # leaf COVERAGE here, never equality (SimClock-only invariant).
        self.obs = obs
        self.embedder = FeatureHashEmbedder()
        self.max_batch = max_batch
        self.prompt_len = prompt_len
        self.max_new = max_new_tokens
        self.controller = controller
        self.model_name = model_name
        # Straggler detection on the serve loop itself: every non-empty
        # step() is timed, and steps beyond the watchdog's trailing-
        # median threshold surface as stats.straggler_steps.
        self.watchdog = watchdog if watchdog is not None else StepWatchdog()
        self.queue: list[Request] = []
        self.stats = EngineStats()
        self._next_id = 0

        cfg = model.cfg
        max_len = self._max_len = prompt_len + max_new_tokens
        self._state_mb_of: dict[int, float] = {}

        # The named scopes label each phase's device ops in a profile
        # (metadata only: the program is the same without them).
        def generate(params, tokens):
            with jax.named_scope("prefill"):
                logits, cache_, kv_len = model.prefill(
                    params, {"tokens": tokens}, max_len)
                tok0 = jnp.argmax(logits[:, :cfg.vocab_size], axis=-1
                                  ).astype(jnp.int32)

            def body(carry, _):
                cache_, kv_len, tok = carry
                logits, cache_, kv_len = model.decode_step(
                    params, cache_, tok, kv_len)
                tok = jnp.argmax(logits[:, :cfg.vocab_size], axis=-1
                                 ).astype(jnp.int32)
                return (cache_, kv_len, tok), tok

            with jax.named_scope("decode"):
                (_, _, _), toks = jax.lax.scan(
                    body, (cache_, kv_len, tok0), None,
                    length=self.max_new - 1)
            return jnp.concatenate([tok0[None], toks], axis=0).T  # (B, new)

        self._generate = jax.jit(generate)

    def _state_mb(self, bucket: int) -> float:
        """MiB of recurrent and KV state a generate of ``bucket`` rows
        carries, from the cache pytree's shapes (once per bucket)."""
        if bucket not in self._state_mb_of:
            shapes = jax.eval_shape(
                lambda: self.model.init_cache(bucket, self._max_len))
            self._state_mb_of[bucket] = sum(
                s.size * s.dtype.itemsize
                for s in jax.tree.leaves(shapes)) / 2**20
        return self._state_mb_of[bucket]

    def _span(self, stage: str, **attrs):
        if self.obs is None:
            return NULL_SPAN
        return self.obs.span(stage, **attrs)

    # ------------------------------------------------------------------ api
    def submit(self, text: str, category: str, prompt_tokens: np.ndarray,
               max_new_tokens: int | None = None) -> int:
        rid = self._next_id
        self._next_id += 1
        self.queue.append(Request(
            req_id=rid, text=text, category=category,
            prompt_tokens=np.asarray(prompt_tokens, np.int32),
            max_new_tokens=max_new_tokens or self.max_new,
            arrival=time.monotonic(),
            traced_at=self.obs.clock.now() if self.obs is not None else 0.0))
        return rid

    def step(self) -> list[Response]:
        """Serve one step from the queue. Returns completed responses.

        Misses a previous lookup left at the head of the queue are
        generated, written back and returned. Otherwise the step looks up
        the next ``max_batch`` requests and returns their hits; their
        misses are generated in the same step only when nothing hit."""
        if not self.queue:
            return []
        if self.queue[0].emb is not None:
            n = next((k for k, r in enumerate(self.queue) if r.emb is None),
                     len(self.queue))
            misses, self.queue = self.queue[:n], self.queue[n:]
            with self._span("engine_step", batch=n, phase="generate"):
                return self._finish(self._generate_misses(misses))
        self.watchdog.step_start()
        batch = self.queue[:self.max_batch]
        self.queue = self.queue[self.max_batch:]
        with self._span("engine_step", batch=len(batch)) as sp:
            hits, misses = self._lookup(batch)
            if hits and misses:
                # The hits go back now; the misses wait, looked up, at
                # the head of the queue for the next step's generate.
                sp.set(phase="lookup")
                self.queue[:0] = misses
                self.stats.hits_before_generate += len(hits)
                self.stats.deferred_generates += 1
                self._stamp(hits)
                return [resp for resp, _ in hits]
            sp.set(phase="lookup+generate" if misses else "lookup")
            return self._finish(hits + self._generate_misses(misses))

    def _lookup(self, batch: list[Request]):
        """Embed and look up ``batch``: (hits as (response, request)
        pairs, misses with their embeddings set)."""
        if self.obs is not None:
            # each request's wait for the step in flight, submit to now,
            # on the recorder's clock (latencies stay on time.monotonic)
            t = self.obs.clock.now()
            for req in batch:
                self.obs.record("queue_wait", req.traced_at, t,
                                category=req.category)

        with self._span("embed", batch=len(batch)):
            embs = self.embedder.embed_batch([r.text for r in batch])
        results = self.cache.lookup_batch(embs, [r.category for r in batch])
        ls = self.cache.last_lookup_stats
        if ls:
            self.stats.search_hops += ls.get("hops", 0)
            self.stats.rows_gathered += ls.get("rows_gathered", 0)

        hits: list[tuple[Response, Request]] = []
        misses: list[Request] = []
        for req, res, emb in zip(batch, results, embs):
            if res.hit:
                hits.append((Response(req.req_id, res.response, None, True,
                                      0.0, req.category, reason=res.reason),
                             req))
                self.stats.served += 1
                self.stats.cache_hits += 1
                self.stats.count_reason(res.reason)
            else:
                req.emb = emb
                misses.append(req)
        return hits, misses

    def _generate_misses(self, misses: list[Request]
                         ) -> list[tuple[Response, Request]]:
        """Generate looked-up misses, write them back in one insert, and
        return them as (response, request) pairs."""
        if not misses:
            return []
        # Pad the miss batch to the search's power-of-two bucket so
        # every miss count shares one compiled generate program; the
        # padding rows (all-zero prompts) are sliced off.
        toks = np.zeros((_bucket_batch(len(misses)), self.prompt_len),
                        np.int32)
        for j, req in enumerate(misses):
            p = req.prompt_tokens[:self.prompt_len]
            toks[j, :len(p)] = p
        prompt_tokens = sum(min(len(r.prompt_tokens), self.prompt_len)
                            for r in misses)
        attrs = {}
        if self.obs is not None:
            attrs["state_mb"] = self._state_mb(toks.shape[0])
        with self._span("model_generate", batch=len(misses),
                        bucket=toks.shape[0],
                        prompt_tokens=prompt_tokens, **attrs):
            out = np.asarray(self._generate(self.params, jnp.asarray(toks)))
        out = out[:len(misses)]
        texts = ["tok:" + ",".join(map(str, out[j]))
                 for j in range(len(misses))]
        # one batched write-back for every miss of the lookup
        self.cache.insert_batch(
            np.stack([r.emb for r in misses]), [r.category for r in misses],
            [r.text for r in misses], texts)
        served = []
        for j, req in enumerate(misses):
            served.append((Response(req.req_id, texts[j], out[j], False,
                                    0.0, req.category, reason="model"),
                           req))
            self.stats.served += 1
            self.stats.model_tokens += out.shape[1]
            self.stats.count_reason("model")
        return served

    def _stamp(self, served: list[tuple[Response, Request]]) -> None:
        """Stamp each response's latency, submit to now, as it is handed
        back; the controller observes the model's answers."""
        t_end = time.monotonic()
        for resp, req in served:
            resp.latency_ms = (t_end - req.arrival) * 1e3
            self.stats.total_latency_ms += resp.latency_ms
            if self.controller is not None and not resp.cached:
                self.controller.observe(self.model_name, LoadSignal(
                    latency_ms=resp.latency_ms,
                    queue_depth=len(self.queue)))

    def _finish(self, served: list[tuple[Response, Request]]
                ) -> list[Response]:
        """Hand back a step that ends its lookup's work: stamp it, and
        end the watchdog's step, begun at the lookup."""
        self._stamp(served)
        self.watchdog.step_end()
        self.stats.straggler_steps = self.watchdog.straggler_events
        return [resp for resp, _ in served]

    def drain(self) -> list[Response]:
        out = []
        while self.queue:
            out.extend(self.step())
        return out
