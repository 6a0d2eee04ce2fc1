"""Public jit'd wrappers around the Pallas kernels.

Responsibilities:
  * shape hygiene — pad N/B/S/d to kernel tile multiples and slice back;
  * backend dispatch — ``interpret=True`` automatically on CPU (this
    container) so the *same call sites* run on TPU (compiled) and CPU
    (interpreted) without flags;
  * dtype policy — bf16 in / fp32 accumulate for attention; fp32 for cache
    scoring (embeddings are fp32, §5.1).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import decode_attention as _dec
from repro.kernels import flash_attention as _fa
from repro.kernels import flat_topk as _ft
from repro.kernels import frontier_hop as _fh
from repro.kernels import gather_scores as _gs
from repro.kernels import mamba_scan as _ms
from repro.kernels import ref as _ref
from repro.kernels import scatter_update as _su


@functools.cache
def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


def _pad_to(x: jax.Array, axis: int, mult: int, value=0) -> tuple[jax.Array, int]:
    n = x.shape[axis]
    target = ((n + mult - 1) // mult) * mult
    if target == n:
        return x, n
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, target - n)
    return jnp.pad(x, pad, constant_values=value), n


def cache_topk(table: jax.Array, valid: jax.Array, queries: jax.Array,
               categories: jax.Array | None = None,
               query_categories: jax.Array | None = None,
               scales: jax.Array | None = None,
               *, block_n: int = 1024, interpret: bool | None = None
               ) -> tuple[jax.Array, jax.Array]:
    """Cache-table cosine top-1 (the 2 ms local search). Any N, B, d.

    Optional ``categories`` (N,) + ``query_categories`` (B,) int32 restrict
    each query's result to its own category (§5.3); pass both or neither
    (exactly one raises — silent fallback would bypass isolation). Padding
    rows/queries are filled with a category no real query can match.

    Optional ``scales`` (N,) fp32 marks the table as int8 with per-row
    symmetric dequant scales: the kernel fuses the dequant into the scan
    (asymmetric scoring — fp32 queries, int8 rows), streaming ~1/4 the
    table bytes. Padding rows get scale 0 (already excluded by valid=0).
    """
    interpret = _on_cpu() if interpret is None else interpret
    if (categories is None) != (query_categories is None):
        raise ValueError("cache_topk: categories and query_categories must "
                         "be passed together (got exactly one)")
    table, n0 = _pad_to(table, 0, block_n)
    valid = jnp.pad(valid.astype(jnp.int8), (0, table.shape[0] - n0))
    if scales is not None:
        scales = jnp.pad(scales.astype(jnp.float32),
                         (0, table.shape[0] - n0))
    if categories is not None:
        # -2: never equals a real category AND is not the -1 wildcard
        # (pad rows are already excluded by valid=0; this is belt-and-braces).
        categories = jnp.pad(categories.astype(jnp.int32),
                             (0, table.shape[0] - n0), constant_values=-2)
    table, d0 = _pad_to(table, 1, 128)
    queries, _ = _pad_to(queries, 1, 128)
    queries, b0 = _pad_to(queries, 0, 8)
    if query_categories is not None:
        # Query-side padding must be NON-negative: the kernel reads any
        # qcat < 0 as a wildcard (full blind scan on the padded lane).
        # int32 max never equals a real category, so pad lanes match
        # nothing; their outputs are sliced off below regardless.
        query_categories = jnp.pad(query_categories.astype(jnp.int32),
                                   (0, queries.shape[0] - b0),
                                   constant_values=jnp.iinfo(jnp.int32).max)
    score, idx = _ft.flat_topk(table, valid, queries, categories,
                               query_categories, scales, block_n=block_n,
                               interpret=interpret)
    return score[:b0], idx[:b0]


def hop_scores(table: jax.Array, indices: jax.Array, queries: jax.Array,
               slot_categories: jax.Array | None = None,
               query_categories: jax.Array | None = None,
               scales: jax.Array | None = None,
               *, interpret: bool | None = None) -> jax.Array:
    """One HNSW frontier hop: gather + dot. indices (B, K), −1 padded.

    With ``slot_categories`` (N,) + ``query_categories`` (B,) the category
    mask is fused into the gather+dot kernel (one-kernel data plane, §5.3).
    Pass both or neither; exactly one raises (silent fallback to the
    unmasked gather would bypass category isolation).

    With ``scales`` (N,) fp32 the table is int8 (per-row symmetric quant):
    rows are widened in VMEM and each dot is scaled by its row's scale —
    no fp32 copy of the table exists.
    """
    interpret = _on_cpu() if interpret is None else interpret
    if (slot_categories is None) != (query_categories is None):
        raise ValueError("hop_scores: slot_categories and query_categories "
                         "must be passed together (got exactly one)")
    table, _ = _pad_to(table, 1, 128)
    queries, _ = _pad_to(queries, 1, 128)
    if slot_categories is not None and query_categories is not None:
        return _gs.gather_scores_masked(table, indices, queries,
                                        slot_categories, query_categories,
                                        scales, interpret=interpret)
    return _gs.gather_scores(table, indices, queries, scales,
                             interpret=interpret)


def frontier_hop(emb: jax.Array, neighbors: jax.Array, meta: jax.Array,
                 frontier: jax.Array, queries: jax.Array,
                 query_categories: jax.Array, done: jax.Array,
                 scales: jax.Array | None = None,
                 *, impl: str | None = None, interpret: bool | None = None
                 ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One fused HNSW beam expansion: neighbor fetch + embedding gather +
    dot + result mask, driven by the scalar-prefetched frontier ids.

    Returns (candidate ids, routing scores, result scores), each (B, F·M).
    Dead lanes — INVALID frontier/neighbor padding, or a *done* query (the
    early-exit freeze) — emit INVALID / -inf and, on the kernel path,
    issue no gather DMAs at all. ``meta`` is the packed per-slot word
    ``category if valid else -2`` (see kernels/frontier_hop.py). With
    ``scales`` (N,) fp32 the embedding table is int8: rows are widened in
    VMEM and the dots scaled per row.

    Dispatch (same pattern as ``scatter_rows``): the Pallas kernel on
    compiled backends, the vectorized jnp reference on CPU/interpret —
    ``impl`` ("pallas" | "ref") forces a path for parity tests.
    """
    interpret = _on_cpu() if interpret is None else interpret
    if impl is None:
        impl = "ref" if interpret else "pallas"
    emb, _ = _pad_to(emb, 1, 128)
    queries, _ = _pad_to(queries, 1, 128)
    if impl == "pallas":
        return _fh.frontier_hop(emb, neighbors, meta, frontier, queries,
                                query_categories, done, scales,
                                interpret=interpret)
    return _ref.frontier_hop_ref(emb, neighbors, meta, frontier, queries,
                                 query_categories, done, scales)


@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_rows_xla(table: jax.Array, rows: jax.Array, vals: jax.Array
                      ) -> jax.Array:
    # XLA in-place scatter (donated buffer) — what the Pallas kernel lowers
    # to conceptually; used directly where interpret-mode Pallas would only
    # add per-row interpreter overhead (CPU).
    return table.at[rows].set(vals.astype(table.dtype))


def scatter_rows(table: jax.Array, rows: jax.Array, vals: jax.Array,
                 *, interpret: bool | None = None) -> jax.Array:
    """Delta flush: write ``vals[r]`` into ``table[rows[r]]`` in place.

    The device-residency sync primitive (``HNSWIndex.device_tables`` is
    the production caller): the input table buffer is donated and
    aliased, so only the R delta rows move — O(delta·d) HBM traffic
    instead of a full O(N·d) re-upload. Dispatch: the Pallas kernel
    serves lane-aligned 2-D tables (row width a multiple of 128 — the
    embedding table, where ~90 % of the bytes live — and a row count
    that is a whole number of its DMA row groups) on compiled backends;
    1-D flag tables (valid/category), narrow tables and odd row counts
    use the XLA in-place scatter, which is already optimal for them and
    avoids off-lane blocks.

    Contract (enforced by callers that pad the delta to a bucket size):
    rows >= 0, duplicate row ids carry identical vals rows.
    """
    interpret = _on_cpu() if interpret is None else interpret
    rows = rows.astype(jnp.int32)
    if (interpret or table.ndim != 2 or table.shape[1] % 128
            or table.shape[0] % _gs.row_group(table.dtype)):
        return _scatter_rows_xla(table, rows, vals)
    return _su.scatter_rows(table, rows, vals)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int | None = None,
                    softcap: float | None = None, kv_offset: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool | None = None) -> jax.Array:
    """Prefill attention; pads Sq/Skv to tile multiples (mask-safe)."""
    interpret = _on_cpu() if interpret is None else interpret
    sq0, skv0 = q.shape[2], k.shape[2]
    q, _ = _pad_to(q, 2, block_q)
    k, _ = _pad_to(k, 2, block_k)
    v, _ = _pad_to(v, 2, block_k)
    # Padding keys would win softmax mass if unmasked: padded kv positions
    # sit beyond skv0; causal masking handles q-padding rows (garbage rows
    # are sliced off). Non-causal calls mask via a window trick is unsound,
    # so we additionally rely on kv_len semantics: here pad keys score ~0
    # only if causal or skv0 == padded length.
    out = _fa.flash_attention(q, k, v, causal=causal, window=window,
                              softcap=softcap, kv_offset=kv_offset,
                              block_q=block_q, block_k=block_k,
                              interpret=interpret)
    return out[:, :, :sq0, :]


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     kv_len: jax.Array, *, softcap: float | None = None,
                     block_k: int = 512, interpret: bool | None = None
                     ) -> jax.Array:
    """Decode one token vs KV cache; ragged kv_len masks padding exactly."""
    interpret = _on_cpu() if interpret is None else interpret
    k, _ = _pad_to(k, 2, block_k)
    v, _ = _pad_to(v, 2, block_k)
    return _dec.decode_attention(q, k, v, kv_len, softcap=softcap,
                                 block_k=block_k, interpret=interpret)


def mamba_scan(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
               C: jax.Array, D: jax.Array, *, block_d: int = 512,
               block_l: int = 64, interpret: bool | None = None
               ) -> tuple[jax.Array, jax.Array]:
    """Selective scan; pads L to block_l (zero dt ⇒ identity steps)."""
    interpret = _on_cpu() if interpret is None else interpret
    L0 = x.shape[1]
    x, _ = _pad_to(x, 1, block_l)
    dt, _ = _pad_to(dt, 1, block_l)   # dt=0 → exp(0·A)=1, dBx=0: state frozen
    B, _ = _pad_to(B, 1, block_l)
    C, _ = _pad_to(C, 1, block_l)
    bd = min(block_d, x.shape[2])
    y, h = _ms.mamba_scan(x, dt, A, B, C, D, block_d=bd, block_l=block_l,
                          interpret=interpret)
    return y[:, :L0], h
