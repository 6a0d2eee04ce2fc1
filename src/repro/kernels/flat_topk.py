"""Tiled cosine top-1 kernel — the hybrid cache's local search (§5.2).

Streams the HBM-resident embedding table through VMEM in (TN, d) tiles,
scores a resident (B, d) query block on the MXU, and keeps a running
(best_score, best_idx) pair per query in VMEM scratch across grid steps
(the TPU grid is sequential, so scratch acts as the reduction carry).

The reduction is CATEGORY-MASKED (§5.3): each table row carries one packed
int32 meta word — its category if valid, ``TOMBSTONE`` (-2) if not, the
same packing as the frontier-hop kernel — each query carries a category,
and rows from another category are treated exactly like invalid rows —
scored -inf so they can never win the top-1. A query category < 0 is a
wildcard (category-blind scan), which is also the path used when no
categories are supplied, so the masked kernel is the only kernel.

At 1 M × 384 fp32 the table is 1.5 GB: the scan is HBM-bandwidth-bound at
~1.9 ms/batch on v5e (819 GB/s) — which is the paper's "2 ms local search"
budget hit with *brute force*; HNSW beam search (``gather_scores``) cuts
the bytes touched to O(hops · beam · M · d). The meta and scale words add
8 bytes/row to the 1536-byte row stream (+0.5 % bandwidth).

The scoring is QUANT-AWARE (asymmetric int8): when the table is stored
int8 with a per-row symmetric scale (``scales`` (N,)), the dequant fuses
into the same scan — the int8 tile streams at 1/4 the bytes, casts to
fp32 in VMEM, dots against the fp32 query block on the MXU, and the
per-row scale multiplies the score column *after* the dot (dequant is
linear per row, so no fp32 table ever materializes in HBM). The fp32
path passes scales = 1, so the masked+scaled kernel stays the only
kernel.

Tiling: TN rows of the table per step (a multiple of 128: the per-row
meta and scale words stream as lane-dense (1, TN) rows), d padded to a
multiple of 128 (384 = 3×128 natively aligned). B is padded to a multiple
of 8 by the wrapper in ``ops.py``. Per-query results are (B, 1) columns;
the argmax is a max then a first-index min over the column iota.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.frontier_hop import TOMBSTONE


def _flat_topk_kernel(table_ref,                   # (TN, d) table tile
                      meta_ref, scale_ref,         # (1, TN) side rows
                      q_ref, qcat_ref,             # resident (B, d), (B, 1)
                      score_out, idx_out,          # (B, 1) outputs
                      best_s, best_i):             # (B, 1) VMEM scratch
    step = pl.program_id(0)
    nsteps = pl.num_programs(0)

    @pl.when(step == 0)
    def _init():
        best_s[...] = jnp.full_like(best_s, -jnp.inf)
        best_i[...] = jnp.full_like(best_i, -1)

    tile = table_ref[...].astype(jnp.float32)            # (TN, d); int8→fp32
    # MXU: (B, d) x (d, TN) -> (B, TN) at fp32 precision (the default
    # may round operands to bf16 — enough to flip a decision near τ).
    # The per-row dequant scale multiplies the score COLUMN after the dot
    # (dequant is linear per row), so the int8 tile never materializes as
    # fp32 in HBM. fp32 tables stream scale = 1 — an exact no-op.
    scores = jax.lax.dot_general(
        q_ref[...], tile, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    scores = scores * scale_ref[...]
    meta = meta_ref[...]                                 # (1, TN) int32
    qcat = qcat_ref[...]                                 # (B, 1) int32
    ok = (meta != TOMBSTONE) & ((qcat < 0) | (meta == qcat))
    scores = jnp.where(ok, scores, -jnp.inf)

    TN = tile.shape[0]
    tile_best = jnp.max(scores, axis=1, keepdims=True)   # (B, 1)
    col = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    # First column holding the max (argmax's tie rule).
    tile_arg = jnp.min(jnp.where(scores == tile_best, col, TN), axis=1,
                       keepdims=True)
    tile_idx = step * TN + tile_arg                      # global row ids

    improved = tile_best > best_s[...]
    best_s[...] = jnp.where(improved, tile_best, best_s[...])
    best_i[...] = jnp.where(improved, tile_idx, best_i[...])

    @pl.when(step == nsteps - 1)
    def _flush():
        score_out[...] = best_s[...]
        idx_out[...] = best_i[...]


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def flat_topk(table: jax.Array, valid: jax.Array, queries: jax.Array,
              categories: jax.Array | None = None,
              query_categories: jax.Array | None = None,
              scales: jax.Array | None = None,
              *, block_n: int = 1024, interpret: bool = False
              ) -> tuple[jax.Array, jax.Array]:
    """Top-1 cosine search. table (N, d) fp32 — or int8 with ``scales``
    (N,) fp32 per-row symmetric dequant scales — valid (N,) int8/bool,
    queries (B, d) fp32 → (best_score (B,), best_idx (B,) int32).

    ``categories`` (N,) int32 + ``query_categories`` (B,) int32 restrict
    each query's result to its own category (< 0 = wildcard). The pair
    travels together — pass both or neither. Exactly one is a
    ``ValueError``: silently degrading to a category-blind scan would be
    a policy-isolation bypass (cross-category reuse is unsound, §5.4),
    and a lone side would otherwise mask everything to -inf.

    Shape requirements (enforced by the ops.py wrapper): N % block_n == 0,
    d % 128 == 0, B % 8 == 0.
    """
    N, d = table.shape
    B = queries.shape[0]
    assert N % block_n == 0, (N, block_n)
    if (categories is None) != (query_categories is None):
        raise ValueError("flat_topk: categories and query_categories must "
                         "be passed together (got exactly one)")
    if categories is None:
        categories = jnp.full((N,), -1, jnp.int32)
        query_categories = jnp.full((B,), -1, jnp.int32)
    if scales is None:
        scales = jnp.ones((N,), jnp.float32)
    # Lane-dense side rows: valid and category packed into one word.
    meta = jnp.where(valid.astype(bool), categories.astype(jnp.int32),
                     TOMBSTONE).reshape(1, N)
    scales = scales.astype(jnp.float32).reshape(1, N)
    qcat = query_categories.astype(jnp.int32).reshape(B, 1)
    grid = (N // block_n,)

    score, idx = pl.pallas_call(
        _flat_topk_kernel,
        name="flat_topk",  # the device trace's instruction name
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i: (i, 0)),   # table tile
            pl.BlockSpec((1, block_n), lambda i: (0, i)),   # meta row
            pl.BlockSpec((1, block_n), lambda i: (0, i)),   # scale row
            pl.BlockSpec((B, d), lambda i: (0, 0)),         # queries resident
            pl.BlockSpec((B, 1), lambda i: (0, 0)),         # query categories
        ],
        out_specs=[
            pl.BlockSpec((B, 1), lambda i: (0, 0)),
            pl.BlockSpec((B, 1), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, 1), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((B, 1), jnp.float32),
            pltpu.VMEM((B, 1), jnp.int32),
        ],
        interpret=interpret,
    )(table, meta, scales, queries, qcat)
    return score[:, 0], idx[:, 0]
