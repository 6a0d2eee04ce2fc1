"""Candidate-row gather + dot kernel — HNSW entry-set scoring (§5.3).

Device beam search needs ``scores[b,k] = <emb[idx[b,k]], q[b]>`` for
candidate node ids that live in an HBM-resident (N, d) table. The ids are
scalar-prefetched (available in SMEM before the grid runs); each grid step
(b, c) takes one chunk of KC candidates of query b and issues one async DMA
per live candidate, all before the first wait, so the copies overlap.

**Aligned row groups.** The TPU lays an (N, d) table out in tiles of
8 rows (32-bit) or 32 rows (int8), and a DMA must move whole tiles along
the row axis. A candidate's DMA therefore moves the aligned group of
``row_group(dtype)`` rows that holds it — 8 · 4 · d bytes for fp32,
32 · d bytes for int8 — and the row is selected in VMEM. The group's other
rows are traffic the chip pays for the alignment rule, not payload.

The selected rows are dotted against the query on the MXU at fp32
precision (``Precision.HIGHEST``; the default may round the operands to
bf16). Bytes touched per hop stay O(B·K·group·d), independent of N.

Masking and dequant live in the jitted wrappers, on the (B, K) score
matrix, not in the kernel: ``gather_scores`` maps padding (idx < 0) to
-inf and — on the int8 tier, where ``scales`` (N,) holds each row's
symmetric dequant scale — multiplies each candidate's dot by its row's
scale (dequant is linear per row, so no fp32 table ever exists in HBM);
``gather_scores_masked`` additionally scores cross-category candidates
-inf (§5.3). A per-candidate 4-byte side word gathered by XLA costs
O(B·K) words, while a DMA of it would move a whole tile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 32      # candidates per grid step (DMAs in flight at once)


def row_group(dtype) -> int:
    """Rows per aligned DMA group: the row tile of the TPU's HBM layout
    (8 rows of a 32-bit type, 32 rows of int8)."""
    return 8 * (4 // jnp.dtype(dtype).itemsize)


def pad_rows(x: jax.Array, mult: int, value=0) -> jax.Array:
    """Pad axis 0 up to a multiple of ``mult`` (a no-op when aligned)."""
    extra = (-x.shape[0]) % mult
    if not extra:
        return x
    pad = [(0, extra)] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, pad, constant_values=value)


def gather_row_dots(emb_any, q, cids, lives, rows_v, grp_v, sel_v, sem):
    """Dot the table rows ``cids`` (scalars, one per candidate) against
    ``q`` (1, d) fp32: returns (1, K) fp32, 0 at dead candidates (callers
    mask). Each live candidate's aligned row group is DMA'd HBM → VMEM
    (every copy started before any wait), its row selected into ``sel_v``
    and the K rows dotted on the MXU. Shared by the gather and
    frontier-hop kernels."""
    G = rows_v.shape[1]

    def copy(m, cid):
        base = pl.multiple_of(cid - cid % G, G)
        return pltpu.make_async_copy(emb_any.at[pl.ds(base, G), :],
                                     rows_v.at[m], sem.at[m])

    for m, (cid, live) in enumerate(zip(cids, lives)):
        @pl.when(live)
        def _start(m=m, cid=cid):
            copy(m, cid).start()

    for m, (cid, live) in enumerate(zip(cids, lives)):
        @pl.when(live)
        def _select(m=m, cid=cid):
            copy(m, cid).wait()
            if rows_v.dtype == jnp.float32:
                row = rows_v[m, pl.ds(cid % G, 1), :]
            else:   # packed rows: widen the group, then pick the row
                grp_v[...] = rows_v[m].astype(jnp.float32)
                row = grp_v[pl.ds(cid % G, 1), :]
            sel_v[pl.ds(m, 1), :] = row

        @pl.when(jnp.logical_not(live))
        def _zero(m=m):
            sel_v[pl.ds(m, 1), :] = jnp.zeros((1, sel_v.shape[1]),
                                              jnp.float32)

    return jax.lax.dot_general(
        q.astype(jnp.float32), sel_v[...], (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def gather_scratch(K: int, d: int, dtype) -> list:
    """VMEM/semaphore scratch for ``gather_row_dots`` over K candidates."""
    G = row_group(dtype)
    return [pltpu.VMEM((K, G, d), dtype),         # DMA'd row groups
            pltpu.VMEM((G, d), jnp.float32),      # widened group (int8)
            pltpu.VMEM((K, d), jnp.float32),      # selected rows
            pltpu.SemaphoreType.DMA((K,))]


def _gather_scores_kernel(idx_ref,               # scalar-prefetched (B, Kp)
                          q_ref,                 # (B, d) queries, resident
                          emb_any,               # (N, d) table, HBM
                          out_ref,               # (C, KC) dots of query b
                          rows_v, grp_v, sel_v, sem):
    b = pl.program_id(0)
    c = pl.program_id(1)
    KC = sel_v.shape[0]
    cids = [idx_ref[b, c * KC + m] for m in range(KC)]
    out_ref[pl.ds(c, 1), :] = gather_row_dots(
        emb_any, q_ref[pl.ds(b, 1), :], cids, [cid >= 0 for cid in cids],
        rows_v, grp_v, sel_v, sem)


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_scores(table: jax.Array, indices: jax.Array, queries: jax.Array,
                  scales: jax.Array | None = None,
                  *, interpret: bool = False) -> jax.Array:
    """table (N, d) fp32 — or int8 with ``scales`` (N,) per-row dequant
    scales — indices (B, K) int32 (−1 = padding); queries (B, d) fp32 →
    scores (B, K) fp32 (−inf at padding). d must be a multiple of 128
    (``ops.hop_scores`` pads it)."""
    return _gather_scores(table, indices, queries, scales, interpret,
                          "gather_scores")


def _gather_scores(table, indices, queries, scales, interpret: bool,
                   name: str) -> jax.Array:
    """``gather_scores``' body; ``name`` is the kernel's instruction name
    in a device trace."""
    B, K = indices.shape
    d = table.shape[1]
    table = pad_rows(table, row_group(table.dtype))
    KC = min(K, CHUNK)
    C = -(-K // KC)
    idx = jnp.pad(indices.astype(jnp.int32), ((0, 0), (0, C * KC - K)),
                  constant_values=-1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, C),
        in_specs=[pl.BlockSpec((B, d), lambda b, c, i: (0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((None, C, KC), lambda b, c, i: (b, 0, 0)),
        scratch_shapes=gather_scratch(KC, d, table.dtype),
    )
    s = pl.pallas_call(
        _gather_scores_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, C, KC), jnp.float32),
        name=name,
        interpret=interpret,
    )(idx, queries.astype(jnp.float32), table).reshape(B, C * KC)[:, :K]
    if scales is not None:
        s = s * jnp.take(scales.astype(jnp.float32),
                         jnp.maximum(indices, 0))
    return jnp.where(indices < 0, -jnp.inf, s)


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_scores_masked(table: jax.Array, indices: jax.Array,
                         queries: jax.Array, slot_categories: jax.Array,
                         query_categories: jax.Array,
                         scales: jax.Array | None = None,
                         *, interpret: bool = False) -> jax.Array:
    """Category-masked entry scoring. table (N, d) fp32 — or int8 with
    ``scales`` (N,) per-row dequant scales — indices (B, K) int32 (−1 =
    padding); queries (B, d) fp32; slot_categories (N,) int32;
    query_categories (B,) int32 (−1 = wildcard) → scores (B, K) fp32
    (−inf at padding and at cross-category candidates)."""
    s = _gather_scores(table, indices, queries, scales, interpret,
                       "gather_scores_masked")
    cat = jnp.take(slot_categories.astype(jnp.int32),
                   jnp.maximum(indices, 0))
    qc = query_categories.astype(jnp.int32)[:, None]
    ok = (qc < 0) | (cat == qc)
    return jnp.where(ok, s, -jnp.inf)
