"""Fused frontier-hop kernel — one full HNSW beam expansion (§5.3).

``gather_scores`` scores candidate ids the *caller* already materialized,
which forces the beam-search loop to expand ``neighbors[frontier]`` with an
XLA gather first — the candidate ids round-trip through an HBM-resident
(B, F, M) buffer and the embedding rows through a materialized
(B, F·M, d) gather every hop. This kernel fuses the whole hop:

    grid (B, F) — one step per frontier lane. The frontier ids are
    scalar-prefetched, so each step's *neighbor rows* arrive via block
    index maps (an SMEM copy whose elements address the DMAs, and a VMEM
    copy for vector ops) before the body runs. The body then issues one
    async DMA per live candidate, pulling the aligned row group that holds
    its embedding row straight from the HBM table into VMEM
    (``gather_scores.gather_row_dots``), and dots the selected rows with
    the query on the MXU at fp32 precision.

Candidate ids therefore never leave the chip, and no (B, F·M, d) gather is
materialized. The neighbor table is blocked 8 rows at a time (the int32
row tile); the step reads the frontier node's row out of that block.

Masking contract (shared with ``ref.frontier_hop_ref``):

* a lane is DEAD when its frontier id is INVALID, the neighbor slot is
  INVALID padding, or the query is done (early-exit freeze). Dead lanes
  issue **no DMAs** and emit id = INVALID, scores = -inf — a finished
  query stops costing HBM bandwidth, it doesn't just stop updating bests;
* routing scores mask only dead lanes (tombstones and cross-category
  nodes still route, DiskANN-style);
* result scores additionally mask by the packed ``meta`` word:
  ``meta[i] = category[i]`` for live slots, ``TOMBSTONE`` (-2) for
  removed ones. A candidate qualifies when ``meta != TOMBSTONE`` and the
  query category matches (< 0 = wildcard).

The kernel emits candidate ids and raw dots; the jitted wrapper applies
the per-candidate side words on the (B, F·M) result — the int8 tier's
per-row dequant scale (``scales`` (N,): the dot of the int8 row, widened
in VMEM, times its scale — no fp32 row ever exists in HBM) and the meta
mask. Those are O(B·F·M) word gathers; a DMA per 4-byte word would move a
whole tile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.gather_scores import (gather_row_dots, gather_scratch,
                                         pad_rows, row_group)

INVALID = -1
TOMBSTONE = -2          # packed meta word for removed (invalid) slots
NBR_GROUP = 8           # neighbor-table rows per block (the int32 row tile)


def _frontier_hop_kernel(frontier_ref,   # scalar-prefetch (B, F) int32
                         done_ref,       # scalar-prefetch (B,) int32
                         nbr_smem,       # (8, M) int32 — candidate ids (addresses)
                         nbr_vmem,       # (8, M) int32 — candidate ids (vector)
                         q_ref,          # (B, d) f32 queries, resident
                         emb_any,        # (N, d) f32/int8, HBM-resident
                         ids_out, dots_out,                # (F, M) blocks
                         rows_v, grp_v, sel_v, sem):
    b = pl.program_id(0)
    f = pl.program_id(1)
    M = sel_v.shape[0]
    fid = frontier_ref[b, f]
    live = (fid >= 0) & (done_ref[b] == 0)
    r = jnp.maximum(fid, 0) % NBR_GROUP
    cids = [nbr_smem[r, m] for m in range(M)]
    dots = gather_row_dots(emb_any, q_ref[pl.ds(b, 1), :], cids,
                           [live & (cid >= 0) for cid in cids],
                           rows_v, grp_v, sel_v, sem)
    ids = nbr_vmem[pl.ds(r, 1), :]                          # (1, M)
    lane = live & (ids >= 0)
    ids_out[pl.ds(f, 1), :] = jnp.where(lane, ids, INVALID)
    dots_out[pl.ds(f, 1), :] = jnp.where(lane, dots, -jnp.inf)


@functools.partial(jax.jit, static_argnames=("interpret",))
def frontier_hop(emb: jax.Array,        # (N, d) f32 or int8, d % 128 == 0
                 neighbors: jax.Array,  # (N, M) int32, INVALID padded
                 meta: jax.Array,       # (N,) int32 packed valid/category
                 frontier: jax.Array,   # (B, F) int32, INVALID padded
                 queries: jax.Array,    # (B, d) f32
                 query_categories: jax.Array,   # (B,) int32, -1 = wildcard
                 done: jax.Array,       # (B,) int32/bool, 1 = frozen query
                 scales: jax.Array | None = None,   # (N,) f32 when emb int8
                 *, interpret: bool = False
                 ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One fused beam expansion. Returns (ids, route, res), each (B, F·M):
    candidate ids (INVALID at dead lanes), routing scores (-inf at dead
    lanes only) and result scores (-inf additionally at tombstoned and
    cross-category candidates)."""
    d = emb.shape[1]
    M = neighbors.shape[1]
    B, F = frontier.shape
    G = row_group(emb.dtype)            # a multiple of NBR_GROUP
    emb = pad_rows(emb, G)
    neighbors = pad_rows(neighbors.astype(jnp.int32), G, INVALID)

    nbr_blk = lambda b, f, fr, dn: (jnp.maximum(fr[b, f], 0) // NBR_GROUP, 0)
    out_blk = pl.BlockSpec((None, F, M), lambda b, f, fr, dn: (b, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, F),
        in_specs=[
            pl.BlockSpec((NBR_GROUP, M), nbr_blk, memory_space=pltpu.SMEM),
            pl.BlockSpec((NBR_GROUP, M), nbr_blk),
            pl.BlockSpec((B, d), lambda b, f, fr, dn: (0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),       # emb (HBM)
        ],
        out_specs=[out_blk, out_blk],
        scratch_shapes=gather_scratch(M, d, emb.dtype),
    )
    ids, route = pl.pallas_call(
        _frontier_hop_kernel,
        name="frontier_hop",  # the device trace's instruction name
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, F, M), jnp.int32),
                   jax.ShapeDtypeStruct((B, F, M), jnp.float32)],
        interpret=interpret,
    )(frontier.astype(jnp.int32), done.astype(jnp.int32), neighbors,
      neighbors, queries.astype(jnp.float32), emb)
    ids = ids.reshape(B, F * M)
    route = route.reshape(B, F * M)
    safe = jnp.maximum(ids, 0)
    if scales is not None:
        route = jnp.where(ids >= 0,
                          route * jnp.take(scales.astype(jnp.float32), safe),
                          -jnp.inf)
    m = jnp.take(meta.astype(jnp.int32), safe)
    qc = query_categories.astype(jnp.int32)[:, None]
    ok = (ids >= 0) & (m != TOMBSTONE) & ((qc < 0) | (m == qc))
    return ids, route, jnp.where(ok, route, -jnp.inf)
