"""Row-scatter update kernel — the device-residency delta flush (§5.2).

The index tables (``emb``, ``neighbors``, ``valid``, ``category``) live
persistently in device HBM; host-side mutations (insert, evict, neighbor
rewires) accumulate in a compact dirty-row log and are applied in place.
A full re-upload is O(capacity·d) HBM traffic per serve step; the scatter
is O(delta·d) — the difference between per-capacity and per-batch sync
cost, which is what keeps the 2 ms local-search budget (§4.4) intact
under a realistic lookup/insert interleave.

Grid: (R / RC,) over chunks of RC delta rows, whose staged values arrive
as one VMEM block. The row ids are scalar-prefetched. The table is an
HBM operand aliased to the output (``input_output_aliases``), so untouched
rows are never copied: the kernel is a true in-place HBM update, not a
rebuild.

The TPU tiles an (N, d) table 8 rows deep (32-bit) or 32 rows deep (int8)
and DMAs move whole tiles along the row axis, so each delta row is a
read-modify-write of the aligned row group that holds it: DMA the group
into VMEM, overwrite the row, DMA the group back. Rows are applied one at
a time, in order, each write finished before the next read, so rows that
share a group compose. Traffic is 2 · group · d · itemsize bytes per delta
row: still O(delta), independent of capacity.

Contract: row ids must be non-negative, duplicate ids must carry
identical ``vals`` rows, and N must be a multiple of the row group
(``ops.scatter_rows`` routes other tables to XLA's scatter).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.gather_scores import row_group

CHUNK = 256     # delta rows per grid step (one staged VMEM block)


def _scatter_rows_kernel(rows_ref,      # scalar-prefetched (R,) int32
                         val_ref,       # (RC, d) staged delta rows, 32-bit
                         table_any,     # (N, d) aliased table (unread)
                         out_any,       # (N, d) the same buffer, HBM
                         grp_v,         # VMEM (G, d) row group, table dtype
                         wide_v,        # VMEM (G, d) 32-bit working copy
                         sem):
    del table_any
    i = pl.program_id(0)
    RC = val_ref.shape[0]
    G = grp_v.shape[0]

    def apply(r, carry):
        row = rows_ref[i * RC + r]
        base = pl.multiple_of(row - row % G, G)
        group = out_any.at[pl.ds(base, G), :]
        load = pltpu.make_async_copy(group, grp_v, sem.at[0])
        load.start()
        load.wait()
        if grp_v.dtype == wide_v.dtype:
            grp_v[pl.ds(row % G, 1), :] = val_ref[pl.ds(r, 1), :]
        else:   # packed rows: edit a widened copy of the group
            wide_v[...] = grp_v[...].astype(wide_v.dtype)
            wide_v[pl.ds(row % G, 1), :] = val_ref[pl.ds(r, 1), :]
            grp_v[...] = wide_v[...].astype(grp_v.dtype)
        store = pltpu.make_async_copy(grp_v, group, sem.at[1])
        store.start()
        store.wait()
        return carry

    jax.lax.fori_loop(0, RC, apply, 0)


@functools.partial(jax.jit, static_argnames=("interpret",), donate_argnums=(0,))
def scatter_rows(table: jax.Array, rows: jax.Array, vals: jax.Array,
                 *, interpret: bool = False) -> jax.Array:
    """In-place row scatter: ``table[rows[r]] = vals[r]`` for each delta row.

    table (N, d) with N a multiple of ``row_group(table.dtype)``; rows
    (R,) int32, all >= 0; vals (R, d) same dtype as table. Returns the
    updated table — the input buffer is donated and aliased, so on device
    this touches only the row groups of the R scattered rows.
    """
    N, d = table.shape
    G = row_group(table.dtype)
    if N % G:
        raise ValueError(f"scatter_rows: {N} table rows is not a multiple "
                         f"of the {G}-row DMA group")
    wide = table.dtype if jnp.dtype(table.dtype).itemsize == 4 else jnp.int32
    rows = rows.astype(jnp.int32)
    vals = vals.astype(table.dtype).astype(wide)
    R = rows.shape[0]
    RC = min(R, CHUNK)
    extra = (-R) % RC
    if extra:   # repeat the last (row, value) pair: a deterministic no-op
        rows = jnp.concatenate([rows, jnp.repeat(rows[-1:], extra)])
        vals = jnp.concatenate([vals, jnp.repeat(vals[-1:], extra, 0)])

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=((R + extra) // RC,),
        in_specs=[pl.BlockSpec((RC, d), lambda i, rows_ref: (i, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.VMEM((G, d), table.dtype),
                        pltpu.VMEM((G, d), wide),
                        pltpu.SemaphoreType.DMA((2,))],
    )
    return pl.pallas_call(
        _scatter_rows_kernel,
        name="scatter_rows",  # the device trace's instruction name
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((N, d), table.dtype),
        input_output_aliases={2: 0},      # table (after rows and vals)
        interpret=interpret,
    )(rows, vals, table)
