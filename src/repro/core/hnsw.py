"""TPU-adapted HNSW index (paper §5, §5.3, §7.4).

The paper's hot loop is CPU HNSW: pointer-chasing greedy traversal with
per-category thresholds applied *during* traversal and early exit on the
first match above threshold. A literal port is hostile to TPU, so the
device-side search is re-blocked for the MXU (see DESIGN.md §3):

* **Host control plane** (this module, numpy): hierarchical HNSW insertion,
  level assignment, neighbor wiring, tombstoning, entry-point maintenance.
  Also an exact hierarchical search used for CPU latency benchmarks.
* **Device data plane** (JAX): *batched fixed-width beam search* over the
  level-0 graph from a multi-entry start set. One hop is the FUSED
  frontier-hop primitive (``repro.kernels.frontier_hop`` via
  ``ops.frontier_hop``): the scalar-prefetched frontier ids drive an
  in-kernel neighbor-row fetch, per-candidate embedding DMAs and the
  masked dot — no XLA-materialized (B, F·M, d) gather — followed by a
  top-F merge. Early exit is the `while_loop` predicate ``best_score ≥
  τ_q`` with a per-query threshold vector — the paper's
  threshold-during-traversal, vectorized — and a *done* query's lanes
  clamp to INVALID inside the hop, so it stops issuing gather DMAs
  entirely. The pure-jnp path here is the portable reference used on CPU
  (``HNSWParams.hop_impl`` selects; None = auto per backend).

Capacity is fixed at construction: tables are preallocated so the jitted
search never recompiles as the cache fills, and the batch dimension is
bucketed to powers of two so every serve batch size B = 1..max_batch
shares one compiled program. ``search_classified`` additionally runs
Algorithm 1's TTL check on device (the ``inserted`` table rides the
delta-sync protocol) and returns {hit, expired, miss} classes.

**Device residency (delta synchronization).** The device tables are
persistent, not a lazily re-uploaded mirror: every host-side mutation
(insert, evict/tombstone, level-0 neighbor rewire) records its touched
rows in a compact dirty-row log, and ``device_tables()`` applies the log
with donated in-place row scatters (``repro.kernels.ops.scatter_rows``:
the Pallas ``scatter_update`` kernel for the lane-aligned embedding
table, XLA scatter for the narrow/flag tables) instead of
re-materializing the full O(capacity·d) tables. A full upload happens only on first use and when
the dirty fraction exceeds ``HNSWParams.rebuild_threshold``. The tiny
entry-point set is re-uploaded on every sync. ``sync_stats`` counts
uploads, rows and bytes moved — the steady-state serve benchmark
(benchmarks/bench_serve.py) asserts sync cost is O(delta) from these.

**Quantized residency (int8 data plane).** With ``emb_dtype="int8"``
(``HNSWParams.emb_dtype`` / the FlatIndex constructor arg) the
device-resident embedding tier is int8 end to end: the host keeps the
fp32 rows as the control plane (graph wiring, exact host search), but
every row is ALSO quantized on write — per-slot symmetric scale,
``q = round(v · 127 / max|v|)`` — and the device tables carry the int8
``emb`` plus a per-slot fp32 ``scale`` table that rides the same
dirty-row delta sync. All three data-plane kernels fuse the dequant into
their dot products (asymmetric scoring: fp32 query, int8 rows, score ×
scale after the dot), so every flat-scan tile moves ~1/4 the bytes and a
category quota holds ~4x the entries per HBM byte. (Gather and scatter
DMAs move aligned row groups — 8 fp32 or 32 int8 rows — so on the chip
their traffic per row is the same for both dtypes; see
kernels/gather_scores.py.) fp32 stays the default and the exact
baseline.
Quantization can shift a score by ~1e-3, so the cache layer re-scores
borderline results (|score − τ| ≤ margin) from the fp32 embedding stored
next to the document (see core/cache.py re-rank tier) — latency may
change at the boundary; the returned candidate's hit/miss decision does
not (see cache.py for the near-tie scope note).

Callers must treat ``device_tables()`` as the *live* mirror: the returned
buffers are donated to the next delta flush, so do not hold references
to them across index mutations — re-fetch per search (``search_batch``
does).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops
from repro.kernels.frontier_hop import TOMBSTONE
from repro.obs.trace import NULL_SPAN

INVALID = -1

# Lookup classification (paper Algorithm 1 lines 12-21), computed ON DEVICE
# inside the jitted search so the cache's Python loop only touches actual
# hits (doc fetch) and expirations (evict):
CLS_MISS, CLS_EXPIRED, CLS_HIT = 0, 1, 2


def _bucket_batch(n: int) -> int:
    """Pad serve batches to the next power of two (min 8 — the fp32
    sublane): engine queue drains produce B = 1..max_batch, and without
    bucketing every distinct B compiles its own program."""
    return max(8, 1 << (max(1, n) - 1).bit_length())


def _pad_query_batch(queries: np.ndarray, thresholds, categories, ttls
                     ) -> tuple[int, int, np.ndarray, np.ndarray,
                                np.ndarray, np.ndarray]:
    """Bucket the batch dimension. Padding lanes get τ = -inf, so they
    are born *done*: beyond the one-time entry-set scoring every query
    pays at init, the frozen hop emits INVALID candidates for them — zero
    per-hop gather DMAs, not just zero result updates."""
    q = np.atleast_2d(np.asarray(queries, np.float32))
    B = q.shape[0]
    Bp = _bucket_batch(B)
    qp = np.zeros((Bp, q.shape[1]), np.float32)
    qp[:B] = q
    taup = np.full(Bp, -np.inf, np.float32)
    taup[:B] = np.broadcast_to(np.asarray(thresholds, np.float32), (B,))
    qcp = np.full(Bp, -1, np.int32)
    if categories is not None:
        qcp[:B] = np.broadcast_to(np.asarray(categories, np.int32), (B,))
    tp = np.full(Bp, np.inf, np.float32)
    if ttls is not None:
        tp[:B] = np.broadcast_to(np.asarray(ttls, np.float32), (B,))
    return B, Bp, qp, taup, qcp, tp


def quantize_rows(vecs: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Per-row symmetric int8 quantization: ``q = round(v / s)`` with
    ``s = max|v| / 127`` — the layout of the quantized resident tier.
    Returns (int8 rows (B, d), fp32 scales (B,)). Zero rows get scale
    eps so the dequant ``q · s`` is exactly zero, never NaN."""
    vecs = np.atleast_2d(np.asarray(vecs, np.float32))
    scale = (np.max(np.abs(vecs), axis=1) / 127.0).astype(np.float32)
    scale = np.maximum(scale, np.float32(1e-12))
    q = np.clip(np.rint(vecs / scale[:, None]), -127, 127).astype(np.int8)
    return q, scale


def _flush_device_tables(device: dict | None, host: dict[str, np.ndarray],
                         dirty: set, capacity: int, rebuild_threshold: float,
                         row_nbytes: int, emb_row_nbytes: int,
                         sync_stats: dict) -> dict:
    """The delta-sync protocol, shared by FlatIndex and HNSWIndex: apply
    the dirty-row log with donated in-place scatters (O(delta) bytes), or
    re-upload everything on first use / past ``rebuild_threshold``
    (negative = always full, the benchmark contrast).
    ``emb_row_nbytes`` is the embedding payload per row (incl. the quant
    scale word), tracked separately — it is the component the int8 tier
    shrinks ~4x, and what the quant benchmark gates on."""
    if device is None or len(dirty) > rebuild_threshold * capacity:
        device = {k: jnp.asarray(v) for k, v in host.items()}
        sync_stats["full_uploads"] += 1
        sync_stats["rows_synced"] += capacity
        sync_stats["bytes_synced"] += capacity * row_nbytes
        sync_stats["emb_bytes_synced"] += capacity * emb_row_nbytes
    elif dirty:
        rows = np.fromiter(dirty, np.int64, len(dirty))
        rows.sort()
        # Bucket the row count (same power-of-two policy as the batch
        # dimension) so the jit cache holds O(log capacity) entries;
        # padding repeats row 0 of the delta with identical payload — a
        # deterministic no-op.
        bucket = _bucket_batch(len(rows))
        rows = np.concatenate(
            [rows, np.full(bucket - len(rows), rows[0])]).astype(np.int32)
        rows_j = jnp.asarray(rows)
        device = {k: ops.scatter_rows(device[k], rows_j,
                                      jnp.asarray(host[k][rows]))
                  for k in host}
        sync_stats["delta_updates"] += 1
        sync_stats["rows_synced"] += len(rows)
        sync_stats["bytes_synced"] += len(rows) * row_nbytes
        sync_stats["emb_bytes_synced"] += len(rows) * emb_row_nbytes
    return device


def _batched_add(index, vecs: np.ndarray,
                 categories: np.ndarray | None) -> np.ndarray:
    """Shared add_batch body: normalize the batch, loop ``index.add``,
    return the (B,) assigned slot ids."""
    vecs = np.atleast_2d(np.asarray(vecs, np.float32))
    B = vecs.shape[0]
    cats = (np.full(B, -1, np.int32) if categories is None
            else np.broadcast_to(np.asarray(categories, np.int32), (B,)))
    slots = np.empty(B, np.int32)
    for i in range(B):
        slots[i] = index.add(vecs[i], category=int(cats[i]))
    return slots


# ---------------------------------------------------------------------------
# Shared device-residency protocol.
# ---------------------------------------------------------------------------

class DeviceResidentIndex:
    """Device-residency + search-observability protocol shared by
    ``FlatIndex`` and ``HNSWIndex``: the version counter, dirty-row log,
    persistent mirror with delta flush (``_flush_device_tables``), sync
    accounting, the embedding-tier dtype (fp32 / int8 with per-slot
    scales), and the searches/compilations/last-search counters. A
    subclass provides ``_host_tables()``, ``_row_nbytes()``,
    ``_rebuild_threshold()`` and (optionally) ``_finish_sync()`` for
    state that rides along on every sync (the HNSW entry set)."""

    @staticmethod
    def span(stage: str, **attrs):
        """A span on the owning cache's recorder: ``SemanticCache`` binds
        its own ``_span`` here. Unowned, or with no recorder, no span."""
        return NULL_SPAN

    def _init_residency(self, emb_dtype: str = "float32") -> None:
        if emb_dtype not in ("float32", "int8"):
            raise ValueError(f"emb_dtype must be 'float32' or 'int8', "
                             f"got {emb_dtype!r}")
        self.emb_dtype = emb_dtype
        if self.quantized:
            # The quantized resident tier: what the device actually holds
            # and the delta sync actually moves. The fp32 ``emb`` host
            # table remains the control plane (graph wiring, exact host
            # search) and is NEVER uploaded in this mode.
            self.emb_q = np.zeros((self.capacity, self.dim), np.int8)
            self.emb_scale = np.zeros((self.capacity,), np.float32)
        self._version = 0
        self._device: dict | None = None
        self._device_version = -1
        # Delta log: rows whose host tables changed since the last device
        # sync. A set — rows touched repeatedly within one serve step
        # coalesce to one scattered row.
        self._dirty: set[int] = set()
        self.sync_stats = {"full_uploads": 0, "delta_updates": 0,
                           "rows_synced": 0, "bytes_synced": 0,
                           "emb_bytes_synced": 0}
        self.search_stats = {"searches": 0, "compilations": 0}
        self._compiled_keys: set = set()
        self.last_search: dict = {}

    @property
    def quantized(self) -> bool:
        return self.emb_dtype == "int8"

    def emb_row_nbytes(self) -> int:
        """Bytes the resident tier moves per embedding row: the row itself
        plus the fp32 dequant scale when quantized — the unit behind both
        the sync and the gather byte counters (~4x smaller at int8)."""
        return self.dim + 4 if self.quantized else self.dim * 4

    def row_nbytes(self) -> int:
        """Bytes one full synced delta row moves (embedding tier + the
        subclass's graph/flag columns) — the public face of the
        ``_row_nbytes`` hook, for benchmarks and reports."""
        return self._row_nbytes()

    def _emb_tables(self) -> dict[str, np.ndarray]:
        """The embedding tier as host tables: the fp32 rows, or the int8
        rows plus the per-slot scale table (which rides the same
        dirty-row delta sync — a row's scale changes exactly when the
        row does)."""
        if self.quantized:
            return {"emb": self.emb_q, "scale": self.emb_scale}
        return {"emb": self.emb}

    def _quantize_slot(self, slot: int, vec: np.ndarray) -> None:
        """Keep the quantized mirror of one row in lockstep with the fp32
        write (callers already mark the row dirty)."""
        if self.quantized:
            q, s = quantize_rows(vec[None])
            self.emb_q[slot] = q[0]        # mirror-ok
            self.emb_scale[slot] = s[0]    # mirror-ok

    def export_rows(self, slots: np.ndarray) -> dict[str, np.ndarray]:
        """Copy the per-slot tables for ``slots`` out of the index — the
        shard-migration export (core/shard.py): the fp32 control-plane
        rows, the category/inserted metadata, and (under int8 residency)
        the quantized rows + scales exactly as the source device holds
        them. All arrays are copies; exporting does not mutate the index
        or its dirty log, so the source keeps serving during a drain."""
        slots = np.asarray(slots, np.int64)
        out = {"emb": self.emb[slots].copy(),
               "category": self.category[slots].copy(),
               "inserted": self.inserted[slots].copy()}
        if self.quantized:
            out["emb_q"] = self.emb_q[slots].copy()
            out["scale"] = self.emb_scale[slots].copy()
        return out

    # -- subclass hooks --------------------------------------------------------
    def _host_tables(self) -> dict:
        raise NotImplementedError

    def _row_nbytes(self) -> int:
        raise NotImplementedError

    def _rebuild_threshold(self) -> float:
        raise NotImplementedError

    def _finish_sync(self, device: dict) -> None:
        pass

    # -- the protocol ----------------------------------------------------------
    def device_tables(self) -> dict:
        """The persistent device mirror, synced to the host state.

        Protocol: no mutation since last sync → returned as-is. Otherwise
        the dirty-row log is applied with one donated in-place scatter
        (O(delta) bytes); a full O(capacity) upload happens only on first
        use or when the dirty fraction exceeds the rebuild threshold.
        Returned buffers are donated to the NEXT flush — re-fetch after
        any mutation, never cache them caller-side. With a recorder
        attached, each flush is a ``delta_flush`` span: ``rows`` distinct
        dirty rows, ``bucket`` rows moved (padded; capacity when full),
        ``full`` 1 for a full upload.
        """
        if self._device is not None and self._device_version == self._version:
            return self._device
        rows, st = len(self._dirty), self.sync_stats
        synced, uploads = st["rows_synced"], st["full_uploads"]
        with self.span("delta_flush", rows=rows) as sp:
            try:
                self._device = _flush_device_tables(
                    self._device, self._host_tables(), self._dirty,
                    self.capacity, self._rebuild_threshold(),
                    self._row_nbytes(), self.emb_row_nbytes(),
                    self.sync_stats)
            except BaseException:
                # A flush that dies mid-delta (device OOM, injected fault)
                # may have DONATED some of the old mirror's buffers to
                # scatters that never completed — the old self._device
                # can no longer be trusted. Drop it so the retry rebuilds
                # the mirror from the (authoritative, untouched) host
                # tables with a clean full upload; the dirty log is
                # preserved unconsumed. tests/test_coherence.py injects
                # exactly this and checks the retried flush restores
                # exact table equality.
                self._device = None
                raise
            sp.set(bucket=st["rows_synced"] - synced,
                   full=st["full_uploads"] - uploads)
            self._finish_sync(self._device)
        self._dirty.clear()
        self._device_version = self._version
        return self._device

    def _record_search(self, B: int, Bp: int, key_extra: tuple = (),
                       stats: dict | None = None) -> None:
        """Count a device search: ``compilations`` is the number of
        distinct compiled signatures seen (padded batch + impl knobs) —
        the bucketing acceptance counter — and ``last_search`` keeps the
        hops/rows-gathered device scalars without forcing a host sync.
        ``gather_row_nbytes`` is the per-row cost of those gathers (the
        int8 tier cuts it ~4x), so callers can derive bytes gathered per
        query without another device round trip."""
        st = self.search_stats
        st["searches"] += 1
        self._compiled_keys.add((Bp,) + tuple(key_extra))
        st["compilations"] = len(self._compiled_keys)
        if stats is None:   # flat scan: the whole table streams per batch
            self.last_search = {"batch": B, "padded_batch": Bp, "hops": 0,
                                "rows_gathered": np.full(B, self.capacity,
                                                         np.int64)}
        else:
            self.last_search = {"batch": B, "padded_batch": Bp,
                                "hops": stats["hops"],
                                "rows_gathered": stats["rows_gathered"][:B]}
        self.last_search["gather_row_nbytes"] = self.emb_row_nbytes()


# ---------------------------------------------------------------------------
# Flat (brute force) index — exact oracle + small-category fast path.
# ---------------------------------------------------------------------------

class FlatIndex(DeviceResidentIndex):
    """Exact cosine top-1 with threshold. O(n·d) per query batch.

    On TPU this is memory-bound at ~1.9 ms per 1M×384 fp32 scan (819 GB/s),
    which is *itself* within the paper's 2 ms local-search budget — see
    EXPERIMENTS.md. Kernel: ``repro.kernels.flat_topk``.

    Search is category-masked (§5.3): each slot carries an int32 category
    id and each query may carry one; a slot only qualifies as a result for
    queries of the same category (query category < 0 = wildcard), so the
    returned neighbor is the best *same-category* match, not the global
    nearest.
    """

    rebuild_threshold: float = 0.25     # delta-sync protocol (see HNSWParams)

    def __init__(self, dim: int, capacity: int, emb_dtype: str = "float32"):
        self.dim = dim
        self.capacity = capacity
        self.emb = np.zeros((capacity, dim), dtype=np.float32)
        self.valid = np.zeros((capacity,), dtype=bool)
        self.category = np.full((capacity,), -1, dtype=np.int32)
        # Insertion timestamps (the cache's slot_inserted aliases this):
        # a device table like emb/valid/category, so TTL classification
        # runs inside the jitted search (Algorithm 1 line 18 on device).
        self.inserted = np.zeros((capacity,), dtype=np.float32)
        self._n = 0
        self._free: list[int] = []
        self._init_residency(emb_dtype)

    def __len__(self) -> int:
        return int(self.valid.sum())

    def add(self, vec: np.ndarray, category: int = -1) -> int:
        slot = self._free.pop() if self._free else self._n
        if slot >= self.capacity:
            raise RuntimeError("FlatIndex full — evict before inserting")
        if slot == self._n:
            self._n += 1
        self.emb[slot] = vec
        self._quantize_slot(slot, np.asarray(vec, np.float32))
        self.valid[slot] = True
        self.category[slot] = category
        self._dirty.add(int(slot))
        self._version += 1
        return slot

    def add_batch(self, vecs: np.ndarray,
                  categories: np.ndarray | None = None) -> np.ndarray:
        """Multi-insert (same signature as HNSWIndex.add_batch).
        Returns the (B,) assigned slot ids."""
        return _batched_add(self, vecs, categories)

    def remove(self, slot: int) -> None:
        if self.valid[slot]:
            self.valid[slot] = False
            self.category[slot] = -1
            self._free.append(slot)
            self._dirty.add(int(slot))
            self._version += 1

    def search_host(self, queries: np.ndarray, thresholds: np.ndarray,
                    ef: int | None = None, *,
                    categories: np.ndarray | None = None
                    ) -> tuple[np.ndarray, np.ndarray]:
        """Returns (idx, score) per query; idx = -1 below threshold.

        ``categories`` (B,) int32 restricts each query's result set to its
        own category (< 0 = no restriction).
        """
        queries = np.atleast_2d(queries)
        if self._n == 0:
            B = queries.shape[0]
            return np.full(B, INVALID, np.int32), np.full(B, -np.inf, np.float32)
        sims = queries @ self.emb[:self._n].T                     # (B, n)
        sims = np.where(self.valid[None, :self._n], sims, -np.inf)
        if categories is not None:
            qc = np.asarray(categories, np.int32).reshape(-1, 1)  # (B, 1)
            allowed = (qc < 0) | (self.category[None, :self._n] == qc)
            sims = np.where(allowed, sims, -np.inf)
        idx = np.argmax(sims, axis=1)
        score = sims[np.arange(len(idx)), idx]
        # isfinite guard: with every slot masked out (empty category, all
        # tombstones) argmax lands on an arbitrary -inf slot, and a -inf
        # threshold would otherwise accept it (-inf >= -inf).
        ok = (score >= thresholds) & np.isfinite(score)
        return (np.where(ok, idx, INVALID).astype(np.int32),
                score.astype(np.float32))

    # -- device path (ops.cache_topk over the resident tables) -----------------
    def _row_nbytes(self) -> int:
        """Bytes one synced delta row moves (emb [+ scale] + valid + cat +
        ts + id)."""
        return self.emb_row_nbytes() + 1 + 4 + 4 + 4

    def _host_tables(self) -> dict:
        return {**self._emb_tables(), "valid": self.valid,
                "category": self.category, "inserted": self.inserted}

    def _rebuild_threshold(self) -> float:
        return self.rebuild_threshold

    def search_batch(self, queries: np.ndarray, thresholds: np.ndarray, *,
                     categories: np.ndarray | None = None
                     ) -> tuple[jax.Array, jax.Array]:
        """Batched device search via the ``flat_topk`` kernel
        (``ops.cache_topk``). Returns DEVICE arrays — convert once at the
        cache layer, not per index call."""
        idx, score, _, _ = self.search_classified(queries, thresholds,
                                                  categories=categories)
        return idx, score

    def search_classified(self, queries: np.ndarray, thresholds: np.ndarray,
                          *, categories: np.ndarray | None = None,
                          ttls: np.ndarray | None = None, now: float = 0.0
                          ) -> tuple[jax.Array, jax.Array, jax.Array,
                                     jax.Array]:
        """Search + on-device TTL classification in one compiled program.
        Returns device (idx, score, cls, cand) with cls ∈ {CLS_MISS,
        CLS_EXPIRED, CLS_HIT} and ``cand`` the best same-category
        candidate BEFORE thresholding (INVALID only when nothing valid
        matched at all) — the cache's fp32 re-rank tier re-scores it when
        the quantized score lands within the τ-margin band. Batch sizes
        are bucketed to powers of two so B = 1..max_batch share one
        compilation."""
        t = self.device_tables()
        B, Bp, qp, taup, qcp, tp = _pad_query_batch(
            queries, thresholds, categories, ttls)
        idx, score, cls, cand = _flat_search_classified(
            t["emb"], t["valid"], t["category"], t["inserted"],
            jnp.asarray(qp), jnp.asarray(taup), jnp.asarray(qcp),
            jnp.asarray(tp), jnp.float32(now), t.get("scale"))
        self._record_search(B, Bp)
        return idx[:B], score[:B], cls[:B], cand[:B]


# ---------------------------------------------------------------------------
# Device-side batched beam search (jnp reference + fused-kernel dispatch).
# ---------------------------------------------------------------------------

def _classify(idx: jax.Array, score: jax.Array, inserted: jax.Array,
              ttls: jax.Array, now: jax.Array) -> jax.Array:
    """Algorithm 1 lines 12-21 on device: {miss, expired, hit} per query
    from the synced ``inserted`` table and the per-query TTL vector."""
    found = idx != INVALID
    age = now - jnp.take(inserted, jnp.maximum(idx, 0))
    expired = found & (age > ttls)
    return jnp.where(expired, CLS_EXPIRED,
                     jnp.where(found, CLS_HIT, CLS_MISS)).astype(jnp.int8)


@jax.jit
def _flat_search_classified(emb, valid, category, inserted, queries, taus,
                            qcat, ttls, now, scale=None):
    score, idx = ops.cache_topk(emb, valid, queries, category, qcat,
                                scales=scale)
    cand = jnp.where(jnp.isfinite(score), idx, INVALID).astype(jnp.int32)
    ok = (score >= taus) & jnp.isfinite(score)
    idx = jnp.where(ok, idx, INVALID).astype(jnp.int32)
    return idx, score, _classify(idx, score, inserted, ttls, now), cand

@partial(jax.jit, static_argnames=("beam", "max_hops", "hop_impl"))
def beam_search(emb: jax.Array,          # (cap, d) float32 or int8 rows
                neighbors: jax.Array,    # (cap, M0) int32, INVALID padded
                valid: jax.Array,        # (cap,) bool
                entries: jax.Array,      # (E,) int32 entry points
                queries: jax.Array,      # (B, d) float32, L2-normalized
                thresholds: jax.Array,   # (B,) float32 per-query τ (category)
                slot_category: jax.Array | None = None,   # (cap,) int32
                query_category: jax.Array | None = None,  # (B,) int32, -1 = any
                scales: jax.Array | None = None,  # (cap,) f32 — emb is int8
                *, beam: int = 32, max_hops: int = 12,
                hop_impl: str = "reference"):
    """Batched fixed-width beam search with per-query threshold early exit.

    Returns (best_idx (B,), best_score (B,), stats) with stats =
    ``{"hops": (), "rows_gathered": (B,), "cand": (B,)}``. best_idx is -1
    where no valid node reached the query's threshold (a cache miss —
    paper Algorithm 1 line 12-14: return immediately, no external access);
    ``stats["cand"]`` keeps the best same-category candidate regardless of
    τ, which the cache's fp32 re-rank tier re-scores for borderline
    queries on the quantized path.

    With ``scales`` (cap,) fp32 the embedding rows are int8 (per-slot
    symmetric quant) and every scoring site — entry-set init, jnp
    reference hop, fused kernel hop — dequantizes inside its dot product
    (asymmetric: fp32 query against int8 rows).

    Tombstoned (invalid) nodes still route traffic (DiskANN-style) but are
    excluded from results. Cross-category nodes get the same treatment
    (§5.3): when ``slot_category``/``query_category`` are given, a node only
    qualifies as a result for queries of its own category (query category
    < 0 = wildcard) — routing stays category-blind so dense regions still
    carry traffic toward sparse ones. Both masks travel as ONE packed
    per-slot ``meta`` word (category, or -2 for tombstones).

    ``hop_impl`` selects the expansion data plane:

    * ``"reference"`` — pure-jnp gathers (the portable CPU oracle);
    * ``"fused"`` — ``ops.frontier_hop``: on compiled backends one Pallas
      kernel per hop fetches the neighbor rows off the level-0 table from
      the scalar-prefetched frontier ids, DMAs the candidate embeddings
      and emits masked scores — no XLA-materialized (B, F·M, d) gather
      ever exists. On CPU it falls back to the jnp reference.
    * ``"fused_pallas"`` — force the kernel (interpret-mode on CPU; the
      parity tests' path).

    DONE-QUERY FREEZE: a query that reached its τ (or a routing fixpoint)
    stops *issuing gathers* — the hop clamps its candidate ids to INVALID
    — instead of merely not updating its best. ``rows_gathered`` counts
    the per-query embedding rows actually fetched (init + hops), the
    deterministic counter the lookup benchmark gates on.
    """
    B = queries.shape[0]
    E = entries.shape[0]
    cap = emb.shape[0]
    # Lane-align d once, outside the hop loop (the kernels require
    # multiples of 128; a no-op for the native 384).
    pad = (-queries.shape[1]) % 128
    if pad:
        emb = jnp.pad(emb, ((0, 0), (0, pad)))
        queries = jnp.pad(queries, ((0, 0), (0, pad)))
    qcat = (jnp.full((B,), -1, jnp.int32) if query_category is None
            else query_category.astype(jnp.int32))
    scat = (jnp.full((cap,), -1, jnp.int32) if slot_category is None
            else slot_category.astype(jnp.int32))
    meta = jnp.where(valid, scat, TOMBSTONE).astype(jnp.int32)
    fused = hop_impl in ("fused", "fused_pallas")
    kernel_impl = "pallas" if hop_impl == "fused_pallas" else None

    def score_nodes(idx):  # idx (B, K) -> cosine scores (B, K)
        safe = jnp.maximum(idx, 0)
        vecs = jnp.take(emb, safe, axis=0).astype(jnp.float32)     # (B,K,d)
        # fp32 precision, as in the kernels: the fused and reference hops
        # must rank candidates alike on the chip too.
        s = jnp.einsum("bkd,bd->bk", vecs, queries,
                       precision=jax.lax.Precision.HIGHEST)
        if scales is not None:      # fused per-row dequant (int8 rows)
            s = s * jnp.take(scales, safe, axis=0)
        return jnp.where(idx == INVALID, -jnp.inf, s)

    def res_mask(idx, scores):  # -inf at non-results (tombstone/category)
        m = jnp.take(meta, jnp.maximum(idx, 0))
        ok = (idx != INVALID) & (m != TOMBSTONE) & \
            ((qcat[:, None] < 0) | (m == qcat[:, None]))
        return jnp.where(ok, scores, -jnp.inf)

    def expand(f_idx, done):
        """One hop: (B, F) frontier -> (B, F·M) candidate (ids, routing
        scores, result scores). Done queries emit INVALID / -inf lanes."""
        if fused:
            return ops.frontier_hop(emb, neighbors, meta, f_idx, queries,
                                    qcat, done.astype(jnp.int32), scales,
                                    impl=kernel_impl)
        nbr = jnp.take(neighbors, jnp.maximum(f_idx, 0), axis=0)
        dead = (f_idx == INVALID)[:, :, None] | done[:, None, None]
        cand = jnp.where(dead, INVALID, nbr).reshape(B, -1)
        route = score_nodes(cand)
        return cand, route, res_mask(cand, route)

    # Initial frontier: entry points (same for all queries), padded to beam.
    if E >= beam:
        f0 = entries.astype(jnp.int32)[:beam]
    else:
        f0 = jnp.concatenate([entries.astype(jnp.int32),
                              jnp.full((beam - E,), INVALID, jnp.int32)])
    f_idx = jnp.broadcast_to(f0[None, :], (B, beam))
    f_score = (ops.hop_scores(emb, f_idx, queries, scales=scales) if fused
               else score_nodes(f_idx))
    f_res = res_mask(f_idx, f_score)
    rows = jnp.sum(f_idx != INVALID, axis=1).astype(jnp.int32)

    best_score = jnp.max(f_res, axis=1)
    best_idx = jnp.take_along_axis(
        f_idx, jnp.argmax(f_res, axis=1)[:, None], axis=1)[:, 0]
    best_idx = jnp.where(jnp.isfinite(best_score), best_idx, INVALID)

    def cond(state):
        hop, _f, _s, _r, _bs, _bi, done, _rows = state
        return (hop < max_hops) & ~jnp.all(done)

    def body(state):
        hop, f_idx, f_score, f_res, best_s, best_i, done, rows = state
        # Expand: one fused hop. Done queries' lanes come back INVALID, so
        # they issue no gather DMAs and cannot re-enter the merge.
        cand, c_route, c_res = expand(f_idx, done)
        rows = rows + jnp.sum(cand != INVALID, axis=1).astype(jnp.int32)

        # Merge frontier ∪ candidates, keep top-beam by raw routing score;
        # the result-masked scores ride along through the same top-k
        # positions (no per-hop validity/category gathers needed).
        all_idx = jnp.concatenate([f_idx, cand], axis=1)
        all_route = jnp.concatenate([f_score, c_route], axis=1)
        all_res = jnp.concatenate([f_res, c_res], axis=1)
        top_s, top_pos = jax.lax.top_k(all_route, beam)
        top_i = jnp.take_along_axis(all_idx, top_pos, axis=1)
        top_r = jnp.take_along_axis(all_res, top_pos, axis=1)

        # Result tracking only over valid (non-tombstoned) same-category
        # nodes — exactly the lanes top_r left finite.
        hop_best_s = jnp.max(top_r, axis=1)
        hop_best_i = jnp.take_along_axis(
            top_i, jnp.argmax(top_r, axis=1)[:, None], axis=1)[:, 0]
        improved = hop_best_s > best_s + 1e-9
        new_best_s = jnp.where(improved, hop_best_s, best_s)
        new_best_i = jnp.where(improved, hop_best_i, best_i)

        # Early exit (paper §5.3): per-query done once τ reached; also stop
        # queries whose frontier reached a fixpoint (the merge returned the
        # previous frontier unchanged — no new candidates route anywhere).
        # Convergence is judged at the ROUTING level, not on the masked
        # best: under category masking the result may stall for hops while
        # the beam traverses a cross-category region.
        converged = jnp.all(top_i == f_idx, axis=1)
        frozen = done[:, None]
        top_i = jnp.where(frozen, f_idx, top_i)
        top_s = jnp.where(frozen, f_score, top_s)
        top_r = jnp.where(frozen, f_res, top_r)
        new_done = done | (new_best_s >= thresholds) | converged
        return (hop + 1, top_i, top_s, top_r, new_best_s, new_best_i,
                new_done, rows)

    done0 = best_score >= thresholds
    state = (jnp.asarray(0), f_idx, f_score, f_res, best_score, best_idx,
             done0, rows)
    hops, _, _, _, best_score, best_idx, _, rows = jax.lax.while_loop(
        cond, body, state)

    hit = best_score >= thresholds
    return (jnp.where(hit, best_idx, INVALID), best_score,
            {"hops": hops, "rows_gathered": rows, "cand": best_idx})


@partial(jax.jit, static_argnames=("beam", "max_hops", "hop_impl"))
def beam_search_classified(emb, neighbors, valid, entries, inserted,
                           queries, thresholds, ttls, now,
                           slot_category=None, query_category=None,
                           scales=None, *,
                           beam: int = 32, max_hops: int = 12,
                           hop_impl: str = "reference"):
    """Algorithm 1 lines 9-21 as ONE compiled program: masked beam search
    plus on-device TTL classification against the synced ``inserted``
    table. Returns (idx, score, cls, stats); the cache's Python loop then
    touches only actual hits and expirations."""
    idx, score, stats = beam_search(
        emb, neighbors, valid, entries, queries, thresholds,
        slot_category, query_category, scales,
        beam=beam, max_hops=max_hops, hop_impl=hop_impl)
    return idx, score, _classify(idx, score, inserted, ttls, now), stats


# ---------------------------------------------------------------------------
# HNSW proper.
# ---------------------------------------------------------------------------

@dataclass
class HNSWParams:
    M: int = 16                 # neighbors per node, upper levels
    M0: int = 32                # neighbors per node, level 0
    ef_construction: int = 64
    ef_search: int = 48         # host-search beam
    beam: int = 32              # device-search beam width F
    max_hops: int = 12          # device-search hop cap
    n_entries: int = 8          # device-search entry set size E
    # Delta-sync protocol: apply dirty rows in place until their fraction
    # of capacity exceeds this, then re-upload the full tables (a graph
    # that churned that much is cheaper to rebuild than to scatter).
    # Negative forces a full upload on every sync (the pre-delta behavior,
    # kept as the O(capacity) contrast for benchmarks).
    rebuild_threshold: float = 0.25
    # Hop data plane: None = auto (the fused frontier-hop kernel on
    # compiled backends, the jnp reference on CPU); "reference" | "fused"
    # | "fused_pallas" force a path (see beam_search).
    hop_impl: str | None = None
    # Device-resident embedding dtype: "float32" (exact baseline) or
    # "int8" (per-slot symmetric scales; every kernel fuses the dequant —
    # ~4x fewer bytes per resident row and per flat-scan tile, ~4x more
    # entries per quota byte). The host keeps fp32 as the control plane.
    emb_dtype: str = "float32"


class HNSWIndex(DeviceResidentIndex):
    """Hierarchical build on host; batched beam search on device.

    Fixed ``capacity``; slots are recycled through a freelist on removal
    (cache eviction). The device tables are persistent: mutations log
    their touched rows in the ``DeviceResidentIndex`` dirty set and
    ``device_tables()`` flushes the log with an in-place scatter (see
    module docstring — sync cost is O(delta), not O(capacity)).
    """

    def __init__(self, dim: int, capacity: int, params: HNSWParams | None = None,
                 seed: int = 0):
        self.dim = dim
        self.capacity = capacity
        self.p = params or HNSWParams()
        self.rng = np.random.default_rng(seed)
        self.ml = 1.0 / math.log(self.p.M)

        self.emb = np.zeros((capacity, dim), dtype=np.float32)
        self.valid = np.zeros((capacity,), dtype=bool)
        self.category = np.full((capacity,), -1, dtype=np.int32)
        # Insertion timestamps (the cache's slot_inserted aliases this) —
        # a device table like the others, riding the same dirty-row delta
        # sync, so TTL classification happens inside the jitted search.
        self.inserted = np.zeros((capacity,), dtype=np.float32)
        self.level = np.full((capacity,), -1, dtype=np.int8)
        # neighbors[0] is the device-visible level-0 graph.
        self.neighbors: list[np.ndarray] = [
            np.full((capacity, self.p.M0), INVALID, dtype=np.int32)
        ]
        self.entry_point: int = INVALID
        self.max_level: int = -1
        self._n = 0
        self._free: list[int] = []
        self._entries_cache: np.ndarray | None = None
        self._entries_version = -1
        self._init_residency(self.p.emb_dtype)

    # -- basic bookkeeping ---------------------------------------------------
    def __len__(self) -> int:
        return int(self.valid.sum())

    def _alloc_slot(self) -> int:
        if self._free:
            return self._free.pop()
        if self._n >= self.capacity:
            raise RuntimeError("HNSWIndex full — evict before inserting")
        slot = self._n
        self._n += 1
        return slot

    def _ensure_level_arrays(self, level: int) -> None:
        while len(self.neighbors) <= level:
            self.neighbors.append(
                np.full((self.capacity, self.p.M), INVALID, dtype=np.int32))

    def _draw_level(self) -> int:
        return int(-math.log(max(self.rng.random(), 1e-12)) * self.ml)

    # -- host greedy search helpers -------------------------------------------
    def _greedy_descend(self, q: np.ndarray, entry: int, level: int) -> int:
        """Greedy 1-best descent at one level (used above the target level)."""
        cur = entry
        cur_sim = float(q @ self.emb[cur])
        improved = True
        nbrs = self.neighbors[level]
        while improved:
            improved = False
            nb = nbrs[cur]
            nb = nb[nb != INVALID]
            if nb.size == 0:
                break
            sims = self.emb[nb] @ q
            j = int(np.argmax(sims))
            if sims[j] > cur_sim:
                cur_sim = float(sims[j])
                cur = int(nb[j])
                improved = True
        return cur

    def _search_level(self, q: np.ndarray, entries: list[int], level: int,
                      ef: int) -> tuple[np.ndarray, np.ndarray]:
        """Best-first search at one level. Returns (ids, sims) sorted desc."""
        nbrs = self.neighbors[level]
        visited = set(entries)
        cand_ids = list(entries)
        cand_sims = list(self.emb[entries] @ q)
        # results kept as parallel arrays, pruned to ef
        res_ids = list(cand_ids)
        res_sims = list(cand_sims)
        while cand_ids:
            j = int(np.argmax(cand_sims))
            c_sim = cand_sims.pop(j)
            c = cand_ids.pop(j)
            worst = min(res_sims) if len(res_sims) >= ef else -np.inf
            if c_sim < worst:
                break
            nb = nbrs[c]
            nb = nb[nb != INVALID]
            nb = [int(x) for x in nb if int(x) not in visited]
            if not nb:
                continue
            visited.update(nb)
            sims = self.emb[nb] @ q
            for node, s in zip(nb, sims):
                if len(res_sims) < ef or s > min(res_sims):
                    res_ids.append(node)
                    res_sims.append(float(s))
                    cand_ids.append(node)
                    cand_sims.append(float(s))
                    if len(res_sims) > ef:
                        k = int(np.argmin(res_sims))
                        res_ids.pop(k)
                        res_sims.pop(k)
        order = np.argsort(res_sims)[::-1]
        return (np.asarray(res_ids, np.int32)[order],
                np.asarray(res_sims, np.float32)[order])

    # -- insertion -------------------------------------------------------------
    def add(self, vec: np.ndarray, category: int = -1) -> int:
        vec = np.asarray(vec, np.float32)
        slot = self._alloc_slot()
        self.emb[slot] = vec
        self._quantize_slot(slot, vec)
        self.valid[slot] = True
        self.category[slot] = category
        lvl = min(self._draw_level(), 8)
        self.level[slot] = lvl
        self._ensure_level_arrays(lvl)
        for l in range(len(self.neighbors)):
            self.neighbors[l][slot] = INVALID
        self._dirty.add(slot)

        if self.entry_point == INVALID:
            self.entry_point = slot
            self.max_level = lvl
            self._version += 1
            return slot

        cur = self.entry_point
        for l in range(self.max_level, lvl, -1):
            cur = self._greedy_descend(vec, cur, l)
        entries = [cur]
        for l in range(min(lvl, self.max_level), -1, -1):
            ids, _sims = self._search_level(vec, entries, l, self.p.ef_construction)
            m = self.p.M0 if l == 0 else self.p.M
            chosen = ids[:m]
            self.neighbors[l][slot, :len(chosen)] = chosen
            # bidirectional wiring with pruning to closest-m
            for nb in chosen:
                row = self.neighbors[l][nb]
                empty = np.where(row == INVALID)[0]
                if empty.size:
                    row[empty[0]] = slot
                else:
                    cand = np.concatenate([row, [slot]])
                    sims = self.emb[cand] @ self.emb[nb]
                    keep = cand[np.argsort(sims)[::-1][:m]]
                    self.neighbors[l][nb] = keep
            if l == 0:     # only the level-0 graph is device-visible
                self._dirty.update(int(nb) for nb in chosen)
            entries = list(ids[:1]) if len(ids) else entries

        if lvl > self.max_level:
            self.max_level = lvl
            self.entry_point = slot
        self._version += 1
        return slot

    def add_batch(self, vecs: np.ndarray,
                  categories: np.ndarray | None = None) -> np.ndarray:
        """Insert a batch of vectors. Returns the (B,) assigned slot ids.

        Graph wiring stays host-sequential (HNSW insertion is inherently
        so), but the whole batch's touched rows coalesce in the delta log,
        so the device pays ONE scatter flush on the next search instead of
        B full-table uploads.
        """
        return _batched_add(self, vecs, categories)

    def remove(self, slot: int) -> None:
        """Tombstone: stays routable until slot reuse, excluded from results."""
        if not self.valid[slot]:
            return
        self.valid[slot] = False
        self.category[slot] = -1
        self._free.append(slot)
        self._dirty.add(int(slot))
        if slot == self.entry_point:
            alive = np.where(self.valid)[0]
            if alive.size:
                lv = self.level[alive]
                best = alive[int(np.argmax(lv))]
                self.entry_point = int(best)
                self.max_level = int(self.level[best])
            else:
                self.entry_point = INVALID
                self.max_level = -1
        self._version += 1

    # -- host search (exact hierarchical; CPU latency benchmarks) --------------
    def search_host(self, queries: np.ndarray, thresholds: np.ndarray,
                    ef: int | None = None, *,
                    categories: np.ndarray | None = None
                    ) -> tuple[np.ndarray, np.ndarray]:
        """Per-query best match above threshold; -1 on miss.

        ``categories`` (B,) int32 masks result tracking by category (< 0 =
        wildcard): traversal stays category-blind — cross-category nodes
        route traffic exactly like tombstones do — but only same-category
        nodes can be returned, so a globally-nearer cross-category neighbor
        no longer shadows a valid same-category match (§5.3).
        """
        queries = np.atleast_2d(np.asarray(queries, np.float32))
        thresholds = np.broadcast_to(np.asarray(thresholds, np.float32),
                                     (queries.shape[0],))
        if categories is not None:
            categories = np.broadcast_to(
                np.asarray(categories, np.int32), (queries.shape[0],))
        ef = ef or self.p.ef_search
        out_idx = np.full(queries.shape[0], INVALID, np.int32)
        out_sim = np.full(queries.shape[0], -np.inf, np.float32)
        if self.entry_point == INVALID:
            return out_idx, out_sim
        for i, q in enumerate(queries):
            entries = [self.entry_point]
            for l in range(self.max_level, 0, -1):
                # small-beam descent (more robust than 1-greedy on the
                # bulk-built pivot graphs; negligible cost on upper levels)
                ids_l, _ = self._search_level(q, entries, l, ef=16)
                entries = [int(x) for x in ids_l[:8]] or entries
            ids, sims = self._search_level(q, entries, 0, ef)
            ok = self.valid[ids]
            if categories is not None and categories[i] >= 0:
                ok &= self.category[ids] == categories[i]
            ids, sims = ids[ok], sims[ok]
            if len(ids) and sims[0] >= thresholds[i]:
                out_idx[i] = ids[0]
                out_sim[i] = sims[0]
            elif len(ids):
                out_sim[i] = sims[0]
        return out_idx, out_sim

    # -- device search ----------------------------------------------------------
    def entry_set(self) -> np.ndarray:
        """Multi-entry start set: entry point + highest-level live nodes.

        Cached on ``_version``: a delta flush re-derives this at most once
        per mutation batch, and selection is O(n) ``argpartition`` (top-E
        by level, order within the set is irrelevant to the beam), not a
        full argsort of all live nodes.
        """
        if self._entries_version == self._version and \
                self._entries_cache is not None:
            return self._entries_cache
        E = self.p.n_entries
        ents = np.full((E,), INVALID, np.int32)
        if self.entry_point != INVALID:
            alive = np.where(self.valid)[0]
            if alive.size > E:
                top = np.argpartition(self.level[alive], alive.size - E)[-E:]
                chosen = alive[top].astype(np.int32)
            else:
                chosen = alive.astype(np.int32)
            ents[:len(chosen)] = chosen
            if self.entry_point not in chosen:
                ents[0] = self.entry_point
        self._entries_cache = ents
        self._entries_version = self._version
        return ents

    def _row_nbytes(self) -> int:
        """Bytes one synced delta row moves (emb [+ scale] + nbrs + valid
        + cat + inserted-timestamp + id)."""
        return (self.emb_row_nbytes()
                + self.neighbors[0].itemsize * self.p.M0
                + self.valid.itemsize + self.category.itemsize
                + self.inserted.itemsize + 4)

    def _host_tables(self) -> dict:
        return {**self._emb_tables(), "neighbors": self.neighbors[0],
                "valid": self.valid, "category": self.category,
                "inserted": self.inserted}

    def _rebuild_threshold(self) -> float:
        return self.p.rebuild_threshold

    def _finish_sync(self, device: dict) -> None:
        # The tiny entry set (E ints) rides along on every sync.
        entries = self.entry_set()
        device["entries"] = jnp.asarray(entries)
        self.sync_stats["bytes_synced"] += entries.nbytes

    def _resolve_hop_impl(self) -> str:
        impl = self.p.hop_impl
        if impl is None:
            impl = "reference" if jax.default_backend() == "cpu" else "fused"
        return impl

    def search_batch(self, queries: np.ndarray, thresholds: np.ndarray, *,
                     categories: np.ndarray | None = None
                     ) -> tuple[jax.Array, jax.Array]:
        """Batched device beam search over the resident tables.

        ``categories`` (B,) int32 per-query category mask (< 0 = wildcard);
        None searches category-blind. The batch dimension is bucketed to
        the next power of two so engine queue drains (B = 1..max_batch)
        share one compiled program, and the returned (idx, score) are
        DEVICE arrays — callers that branch on them convert ONCE at their
        layer instead of this method forcing a blocking host sync on both
        outputs. Per-search hops/rows-gathered stats (device scalars, no
        sync) land in ``self.last_search``.
        """
        t = self.device_tables()
        B, Bp, qp, taup, qcp, _ = _pad_query_batch(
            queries, thresholds, categories, None)
        impl = self._resolve_hop_impl()
        idx, score, stats = beam_search(
            t["emb"], t["neighbors"], t["valid"], t["entries"],
            jnp.asarray(qp), jnp.asarray(taup), t["category"],
            jnp.asarray(qcp), t.get("scale"), beam=self.p.beam,
            max_hops=self.p.max_hops, hop_impl=impl)
        self._record_search(B, Bp,
                            ("beam", self.p.beam, self.p.max_hops, impl),
                            stats)
        return idx[:B], score[:B]

    def search_classified(self, queries: np.ndarray, thresholds: np.ndarray,
                          *, categories: np.ndarray | None = None,
                          ttls: np.ndarray | None = None, now: float = 0.0
                          ) -> tuple[jax.Array, jax.Array, jax.Array,
                                     jax.Array]:
        """Beam search + on-device TTL classification in one compiled
        program (Algorithm 1 lines 9-21): returns device (idx, score, cls,
        cand) with cls ∈ {CLS_MISS, CLS_EXPIRED, CLS_HIT}, judged against
        the synced ``inserted`` table, per-query ``ttls`` and ``now``;
        ``cand`` is the best same-category candidate BEFORE the τ test
        (the cache's fp32 re-rank tier re-scores it at the boundary on
        the quantized path)."""
        t = self.device_tables()
        B, Bp, qp, taup, qcp, tp = _pad_query_batch(
            queries, thresholds, categories, ttls)
        impl = self._resolve_hop_impl()
        idx, score, cls, stats = beam_search_classified(
            t["emb"], t["neighbors"], t["valid"], t["entries"],
            t["inserted"], jnp.asarray(qp), jnp.asarray(taup),
            jnp.asarray(tp), jnp.float32(now), t["category"],
            jnp.asarray(qcp), t.get("scale"), beam=self.p.beam,
            max_hops=self.p.max_hops, hop_impl=impl)
        self._record_search(B, Bp,
                            ("classified", self.p.beam, self.p.max_hops,
                             impl), stats)
        return idx[:B], score[:B], cls[:B], stats["cand"][:B]

    # -- bulk build (benchmarks) -------------------------------------------------
    @classmethod
    def bulk_build(cls, vecs: np.ndarray, capacity: int | None = None,
                   params: HNSWParams | None = None, seed: int = 0,
                   categories: np.ndarray | None = None) -> "HNSWIndex":
        """Pivot-clustered approximate build: O(n·√n·d), for large benchmark
        indexes where incremental insertion would dominate runtime.

        ``categories`` (n,) int32 assigns per-slot categories (the masked
        search input, §5.3); omitted → -1 (matched only by wildcard
        queries, i.e. category-blind search still works)."""
        n, dim = vecs.shape
        capacity = capacity or int(n * 1.25) + 8
        idx = cls(dim, capacity, params, seed)
        if categories is not None:
            idx.category[:n] = np.asarray(categories, np.int32)
        p = idx.p
        n_piv = max(1, int(math.sqrt(n) * 2))
        rng = np.random.default_rng(seed)
        piv = rng.choice(n, size=min(n_piv, n), replace=False)
        pivots = vecs[piv]
        sims_pv = vecs @ pivots.T                               # (n, P)
        assign = np.argmax(sims_pv, axis=1)
        # overlap: second-best pivot too, for boundary connectivity
        assign2 = np.argsort(-sims_pv, axis=1)[:, 1] if pivots.shape[0] > 1 \
            else assign
        idx.emb[:n] = vecs
        if idx.quantized:
            idx.emb_q[:n], idx.emb_scale[:n] = quantize_rows(vecs)
        idx.valid[:n] = True
        idx.level[:n] = 0
        idx._n = n
        piv_nodes = piv.astype(np.int64)      # pivots ARE real points
        for c in range(pivots.shape[0]):
            members = np.where((assign == c) | (assign2 == c))[0]
            if members.size <= 1:
                continue
            sims = vecs[members] @ vecs[members].T
            np.fill_diagonal(sims, -np.inf)
            k = min(p.M0 - 2, members.size - 1)   # leave room for hub edges
            nn = np.argpartition(-sims, k - 1, axis=1)[:, :k]
            idx.neighbors[0][members[:, None].repeat(k, 1),
                             np.arange(k)[None, :]] = members[nn]
            # hub edges: every member ↔ its pivot keeps the graph connected
            idx.neighbors[0][members, p.M0 - 1] = piv_nodes[c]
        # pivot-to-pivot kNN edges (level 0 + level 1) bridge clusters
        psims = pivots @ pivots.T
        np.fill_diagonal(psims, -np.inf)
        kp = min(p.M, piv_nodes.size - 1)
        idx._ensure_level_arrays(1)
        idx.level[piv_nodes] = 1
        if kp > 0:
            pnn = np.argpartition(-psims, kp - 1, axis=1)[:, :kp]
            for j, node in enumerate(piv_nodes):
                idx.neighbors[1][node, :kp] = piv_nodes[pnn[j]]
                idx.neighbors[0][node, p.M0 - kp - 1:p.M0 - 1] = \
                    piv_nodes[pnn[j][:kp]]
        idx.entry_point = int(piv_nodes[0])
        idx.max_level = 1
        # Every row was written above; log them all dirty. The first sync
        # is a full upload anyway (no device mirror exists yet), but a
        # build into a PRE-SYNCED index must not skip the delta log.
        idx._dirty.update(range(n))
        idx._version += 1
        return idx
