import os
if "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Dry-run of the PAPER'S OWN data plane: the batched semantic-cache
lookup + index maintenance, lowered + compiled on the production mesh.

Two implementations of the 2 ms local search (§5.2):
    flat — tiled cosine top-1 over the whole table (O(N·d) HBM stream)
    beam — HNSW batched-frontier beam search (O(hops·beam·M·d) gathers)

Plus the write side of the device-resident index:
    delta — the per-step delta flush (donated in-place row scatter over
            emb/neighbors/valid/category). Its "bytes accessed" must scale
            with --delta-rows, not --entries: the dry-run proof that
            steady-state sync cost is O(delta) while the seed's full
            re-upload was O(capacity).

Sharding: the index is replicated per data-group (reads need no
collectives); queries shard over (pod, data). A category-sharded variant
shards the TABLE over data (each group holds a category shard, §7.4) and
is what the router's shard_for() maps onto.

    PYTHONPATH=src python -m repro.launch.cache_dryrun \
        [--entries 1048576] [--batch 128] [--impl flat|beam|both]

Results → results/dryrun_cache/cache__<impl>__<mesh>.json
"""

import argparse
import json
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis import hlo_cost
from repro.core.hnsw import beam_search
from repro.distributed.context import Dist
from repro.launch.mesh import make_production_mesh

RESULTS = "results/dryrun_cache"


def flat_lookup(emb, valid, queries, thresholds, slot_cat, query_cat):
    """Pure-jnp tiled top-1 (XLA path of kernels/flat_topk), category-masked."""
    scores = jnp.einsum("nd,bd->bn", emb, queries,
                        preferred_element_type=jnp.float32)
    ok = valid[None, :] & ((query_cat[:, None] < 0) |
                           (slot_cat[None, :] == query_cat[:, None]))
    scores = jnp.where(ok, scores, -jnp.inf)
    best = jnp.argmax(scores, axis=1).astype(jnp.int32)
    best_s = jnp.take_along_axis(scores, best[:, None], axis=1)[:, 0]
    hit = best_s >= thresholds
    return jnp.where(hit, best, -1), best_s


def delta_flush(emb, nbrs, valid, cat, rows, emb_rows, nbr_rows,
                valid_rows, cat_rows):
    """Donated in-place scatter of R dirty rows into the resident tables
    (the XLA form of kernels/scatter_update, as HNSWIndex applies it)."""
    return (emb.at[rows].set(emb_rows), nbrs.at[rows].set(nbr_rows),
            valid.at[rows].set(valid_rows), cat.at[rows].set(cat_rows))


def build(impl: str, multi_pod: bool, n_entries: int, batch: int,
          dim: int = 384, m0: int = 32, shard_table: bool = False,
          dtype="f32", delta_rows: int = 256):
    mesh = make_production_mesh(multi_pod=multi_pod)
    dist = Dist.from_mesh(mesh)
    ns = lambda s: NamedSharding(mesh, s)
    b_axes = dist.batch_axes
    sds = jax.ShapeDtypeStruct
    # Category-sharded table (§7.4) splits N over data; replicated default.
    table_spec = P(dist.data_axis, None) if shard_table else P(None, None)

    emb_dt = jnp.bfloat16 if dtype == "bf16" else jnp.float32
    emb = sds((n_entries, dim), emb_dt)
    valid = sds((n_entries,), jnp.bool_)
    slot_cat = sds((n_entries,), jnp.int32)
    nbrs = sds((n_entries, m0), jnp.int32)
    entries = sds((8,), jnp.int32)
    queries = sds((batch, dim), jnp.float32)
    taus = sds((batch,), jnp.float32)
    qcat = sds((batch,), jnp.int32)

    if impl == "delta":
        R = delta_rows
        rep2, rep1 = ns(P(None, None)), ns(P(None))
        fn = jax.jit(delta_flush, donate_argnums=(0, 1, 2, 3),
                     in_shardings=(rep2, rep2, rep1, rep1, rep1,
                                   rep2, rep2, rep1, rep1),
                     out_shardings=(rep2, rep2, rep1, rep1))
        lowered = fn.lower(emb, nbrs, valid, slot_cat,
                           sds((R,), jnp.int32),
                           sds((R, dim), emb_dt), sds((R, m0), jnp.int32),
                           sds((R,), jnp.bool_), sds((R,), jnp.int32))
    elif impl == "flat":
        fn = jax.jit(flat_lookup,
                     in_shardings=(ns(table_spec), ns(P(table_spec[0])),
                                   ns(P(b_axes, None)), ns(P(b_axes)),
                                   ns(P(table_spec[0])), ns(P(b_axes))),
                     out_shardings=(ns(P(b_axes)), ns(P(b_axes))))
        lowered = fn.lower(emb, valid, queries, taus, slot_cat, qcat)
    else:
        fn = jax.jit(
            lambda e, nb, v, en, q, t, sc, qc: beam_search(
                e, nb, v, en, q, t, sc, qc, beam=32, max_hops=12),
            in_shardings=(ns(P(None, None)), ns(P(None, None)),
                          ns(P(None)), ns(P(None)),
                          ns(P(b_axes, None)), ns(P(b_axes)),
                          ns(P(None)), ns(P(b_axes))),
            out_shardings=(ns(P(b_axes)), ns(P(b_axes)), None))
        lowered = fn.lower(emb, nbrs, valid, entries, queries, taus,
                           slot_cat, qcat)

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    cost = {k: float(v) for k, v in (compiled.cost_analysis() or {}).items()
            if isinstance(v, (int, float))}
    parsed = hlo_cost.analyze(compiled.as_text()).to_dict()
    mem = compiled.memory_analysis()
    mem_dict = {a: int(getattr(mem, a)) for a in
                ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes") if hasattr(mem, a)}
    n_dev = 512 if multi_pod else 256
    esz = 2 if dtype == "bf16" else 4
    row_bytes = dim * esz + m0 * 4 + 1 + 4
    payload = {
        "arch": f"cache_{impl}" + ("_sharded" if shard_table else ""),
        "shape": (f"delta_r{delta_rows}_n{n_entries}" if impl == "delta"
                  else f"lookup_b{batch}_n{n_entries}"),
        "mesh": "multi" if multi_pod else "single",
        "n_devices": n_dev,
        "compile_s": round(t_compile, 2),
        "memory_analysis": mem_dict,
        "cost_analysis": cost,
        "hlo_cost": parsed,
        # ideal: stream the (replicated) table once per query batch;
        # the delta flush streams only the dirty rows
        "model_flops": 0.0 if impl == "delta"
        else 2.0 * n_entries * dim * batch,
        "active_params": 0,
        "cache_bytes": 0,
        "table_bytes": n_entries * dim * esz,
        "delta_bytes": delta_rows * row_bytes if impl == "delta" else 0,
    }
    return payload


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--entries", type=int, default=1 << 20)
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--impl", default="both",
                    help="flat | beam | delta | both (flat+beam) | all")
    ap.add_argument("--delta-rows", type=int, default=256,
                    help="delta impl: dirty rows per flush")
    ap.add_argument("--shard-table", action="store_true")
    ap.add_argument("--dtype", default="f32", choices=["f32", "bf16"])
    ap.add_argument("--out", default=RESULTS)
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)
    impls = {"both": ["flat", "beam"],
             "all": ["flat", "beam", "delta"]}.get(args.impl, [args.impl])
    for impl in impls:
        for mp in (False, True):
            name = impl + ("_sharded" if args.shard_table else "") + \
                ("_bf16" if args.dtype == "bf16" else "")
            tag = f"cache__{name}__{'multi' if mp else 'single'}"
            print(f"[cache-dryrun] {tag} ...", flush=True)
            payload = build(impl, mp, args.entries, args.batch,
                            shard_table=args.shard_table, dtype=args.dtype,
                            delta_rows=args.delta_rows)
            with open(os.path.join(args.out, tag + ".json"), "w") as f:
                json.dump(payload, f, indent=1)
            cost = payload["cost_analysis"]
            flops = cost.get("flops", 0.0)
            byts = cost.get("bytes accessed", 0.0)
            print(f"  flops={flops:.3e} bytes={byts:.3e} "
                  f"mem_ms={byts / 819e9 * 1e3:.3f} "
                  f"coll={payload['hlo_cost']['total_collective_bytes']:.3e}")


if __name__ == "__main__":
    main()
