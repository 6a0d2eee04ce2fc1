"""Mixture-of-Experts FFN with production expert parallelism.

Two implementations sharing one routing function:

``moe_ffn_dense_exact``
    Every held expert on every token, then the routed combine. Exact;
    the one-chip path and the oracle for the EP path. A layer may hold a
    share of the experts (``cfg.experts_held`` from ``cfg.experts_first``):
    it routes over all ``n_experts`` and returns only its own experts'
    part, as one expert-parallel shard would.

``moe_ffn_ep``
    Production path under ``shard_map``: experts are owned by ``data``
    shards (the token axis) and each expert's FFN width is sharded over
    ``model``. Token routing is sort-based and dropping (capacity factor):

        route (outside, replicated math) → per-destination send buffers
        → all_to_all over ``data`` → sort by local expert → ragged_dot
        grouped GEMMs (w_gate/w_up/w_down slices) → psum over ``model``
        (ffn partial sums) → all_to_all back → weighted scatter-combine.

    Buffer bytes per device ≈ n_data·C·d ≈ T_loc·top_k·capacity·d — kept
    small by chunked prefill (``cfg.prefill_chunk``; kimi-k2 sets it).
    Experts are zero-padded to a multiple of ``n_data``
    (router logits for padding = −inf, so they never receive tokens).

The paper's technique (semantic caching) sits in front of any of this;
EP here is serving/training substrate the 1T-param assigned arch needs.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed.context import Dist
from repro.models.layers import swiglu


def _ragged_dot(lhs, rhs, group_sizes):
    """Grouped GEMM with fp32 accumulation; its transpose returns
    cotangents in the operand dtypes (bf16 in, bf16 cts out)."""
    return jax.lax.ragged_dot(lhs, rhs, group_sizes,
                              preferred_element_type=jnp.float32)


def padded_experts(n_experts: int, n_data: int) -> int:
    return int(math.ceil(n_experts / n_data) * n_data)


def route(x: jax.Array, router_w: jax.Array, cfg, n_expert_pad: int
          ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Top-k routing. x (T, d) → ids (T, k) int32, weights (T, k) fp32,
    aux load-balancing loss (scalar, switch-style)."""
    logits = x.astype(jnp.float32) @ router_w.astype(jnp.float32)  # (T, E)
    E = cfg.n_experts
    if n_expert_pad > E:
        pad = jnp.full((logits.shape[0], n_expert_pad - E), -1e30, jnp.float32)
        logits = jnp.concatenate([logits, pad], axis=1)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, ids = jax.lax.top_k(probs, cfg.moe_top_k)
    weights = weights / jnp.maximum(weights.sum(axis=-1, keepdims=True), 1e-9)
    # Switch aux loss: E · Σ_e f_e · P_e  (over real experts only).
    f = jnp.zeros((n_expert_pad,), jnp.float32).at[ids.reshape(-1)].add(1.0)
    f = f / jnp.maximum(f.sum(), 1.0)
    p_mean = probs.mean(axis=0)
    aux = E * jnp.sum(f[:E] * p_mean[:E])
    return ids.astype(jnp.int32), weights, aux


def moe_ffn_dense_exact(x: jax.Array, p: dict, cfg) -> tuple[jax.Array, jax.Array]:
    """Exact: every held expert applied to every token, then the weighted
    combine of the routes that land on held experts (routes to absent
    experts add nothing). O(T·E_held) compute."""
    ids, weights, aux = route(x, p["router"], cfg, cfg.n_experts)
    if cfg.experts_first:
        ids = ids - cfg.experts_first
    xf = x.astype(jnp.float32)
    g = jnp.einsum("td,edf->etf", xf, p["w_gate"].astype(jnp.float32))
    u = jnp.einsum("td,edf->etf", xf, p["w_up"].astype(jnp.float32))
    h = jax.nn.silu(g) * u
    y_all = jnp.einsum("etf,efd->etd", h, p["w_down"].astype(jnp.float32))
    onehot = jax.nn.one_hot(ids, cfg.n_experts_held,
                            dtype=jnp.float32)                       # (T,k,E)
    combine = (weights[..., None] * onehot).sum(axis=1)              # (T,E)
    y = jnp.einsum("etd,te->td", y_all, combine)
    return y.astype(x.dtype), aux


def _capacity(t_loc: int, top_k: int, n_data: int, factor: float) -> int:
    c = int(math.ceil(t_loc * top_k / n_data * factor))
    return max(8, ((c + 7) // 8) * 8)


def _moe_local(x, ids, weights, w_gate, w_up, w_down, *, cfg, n_data: int,
               e_pad: int, data_axis: str, model_axis: str | None,
               rs_combine: bool = False):
    """Per-device body under shard_map. x (T_loc, d); expert slices
    w_gate/w_up (E_loc, d, ff_loc), w_down (E_loc, ff_loc, d).

    ``rs_combine``: reduce-scatter the down-proj partials over ``model``
    onto the d axis instead of a full psum, return tokens d-sharded, and
    let GSPMD all-gather d once at the residual — cuts the model-axis
    collective ~2× and the return all_to_all ~n_model× (§Perf B iter 2).
    """
    T_loc, d = x.shape
    k = cfg.moe_top_k
    e_loc = e_pad // n_data
    my = jax.lax.axis_index(data_axis)

    flat_ids = ids.reshape(-1)                                  # (N=T_loc·k,)
    flat_w = weights.reshape(-1)
    tok_idx = jnp.repeat(jnp.arange(T_loc, dtype=jnp.int32), k)
    dest = flat_ids // e_loc                                    # owner shard
    N = flat_ids.shape[0]
    C = _capacity(T_loc, k, n_data, cfg.capacity_factor)

    # Stable sort by destination; position within each destination group.
    order = jnp.argsort(dest, stable=True)
    s_dest = dest[order]
    s_tok = tok_idx[order]
    s_eid = flat_ids[order]
    starts = jnp.searchsorted(s_dest, jnp.arange(n_data, dtype=s_dest.dtype))
    pos = jnp.arange(N, dtype=jnp.int32) - starts[s_dest].astype(jnp.int32)
    keep = pos < C                                              # drop overflow
    slot = jnp.where(keep, s_dest * C + pos, n_data * C)        # OOB → dropped

    send_tok = jnp.zeros((n_data * C, d), x.dtype).at[slot].set(
        x[s_tok], mode="drop")
    send_eid = jnp.full((n_data * C,), -1, jnp.int32).at[slot].set(
        s_eid, mode="drop")

    # all_to_all over data: shard i's block j → shard j's block i.
    recv_tok = jax.lax.all_to_all(send_tok.reshape(n_data, C, d), data_axis,
                                  split_axis=0, concat_axis=0, tiled=True)
    recv_eid = jax.lax.all_to_all(send_eid.reshape(n_data, C), data_axis,
                                  split_axis=0, concat_axis=0, tiled=True)

    # Local expert compute: group rows by local expert for ragged GEMMs.
    rows = recv_tok.reshape(-1, d)
    leid = recv_eid.reshape(-1) - my * e_loc
    invalid = (recv_eid.reshape(-1) < 0) | (leid < 0) | (leid >= e_loc)
    leid = jnp.where(invalid, e_loc, leid)                      # sort last
    g_order = jnp.argsort(leid, stable=True)
    rows = rows[g_order]
    gs = jnp.bincount(leid, length=e_loc + 1)[:e_loc]           # valid only

    h = _ragged_dot(rows, w_gate.astype(rows.dtype), gs)
    u = _ragged_dot(rows, w_up.astype(rows.dtype), gs)
    hidden = (jax.nn.silu(h) * u).astype(x.dtype)
    part = _ragged_dot(hidden, w_down.astype(hidden.dtype), gs)  # (M, d)
    d_out = d
    if model_axis is not None:
        if rs_combine:
            # (M, d) partials → (M, d/n_model) summed shard
            part = jax.lax.psum_scatter(part, model_axis,
                                        scatter_dimension=1, tiled=True)
            d_out = part.shape[1]
        else:
            part = jax.lax.psum(part, model_axis)               # ffn partials

    # Unsort, return to senders, weighted combine.
    part = part.astype(x.dtype)
    unsorted = jnp.zeros_like(part).at[g_order].set(part)
    back = jax.lax.all_to_all(unsorted.reshape(n_data, C, d_out), data_axis,
                              split_axis=0, concat_axis=0, tiled=True)
    flat_back = back.reshape(n_data * C, d_out)
    contrib = flat_back[jnp.clip(slot, 0, n_data * C - 1)]      # (N, d_out)
    contrib = jnp.where(keep[:, None], contrib.astype(jnp.float32), 0.0)
    y = jnp.zeros((T_loc, d_out), jnp.float32).at[s_tok].add(
        contrib * flat_w[order][:, None])
    return y.astype(x.dtype)


def moe_ffn_ep(x: jax.Array, p: dict, cfg, dist: Dist,
               token_parallel: bool = False) -> tuple[jax.Array, jax.Array]:
    """Expert-parallel MoE. x (T, d) global. Returns (y (T, d), aux).

    Default layout: tokens sharded over (pod, data), replicated over
    ``model``; each expert's FFN width splits over ``model`` with a psum
    of the down-proj partials.

    ``token_parallel`` (small-expert archs, ffe < 128·n_model): tokens
    shard over (pod, data, **model**) and each shard runs FULL-width
    expert FFNs for its slice — no model-axis psum, 1/n_model the
    per-device routing bytes, MXU-aligned GEMMs (§Perf A iteration 3).
    """
    if cfg.n_experts_held != cfg.n_experts:
        raise ValueError(f"{cfg.name}: the EP path shards all n_experts "
                         "itself; it takes no held share")
    n_data = dist.n_data
    e_pad = padded_experts(cfg.n_experts, n_data)
    ids, weights, aux = route(x, p["router"], cfg, e_pad)

    batch = dist.batch_axes
    w_gate, w_up, w_down = p["w_gate"], p["w_up"], p["w_down"]
    if e_pad > cfg.n_experts:
        padn = e_pad - cfg.n_experts
        w_gate = jnp.pad(w_gate, ((0, padn), (0, 0), (0, 0)))
        w_up = jnp.pad(w_up, ((0, padn), (0, 0), (0, 0)))
        w_down = jnp.pad(w_down, ((0, padn), (0, 0), (0, 0)))

    if token_parallel and dist.n_model > 1:
        tok_axes = (*batch, dist.model_axis)
        body = functools.partial(_moe_local, cfg=cfg, n_data=n_data,
                                 e_pad=e_pad, data_axis=dist.data_axis,
                                 model_axis=None)
        y = jax.shard_map(
            body, mesh=dist.mesh,
            in_specs=(P(tok_axes, None), P(tok_axes, None),
                      P(tok_axes, None),
                      P(dist.data_axis, None, None),
                      P(dist.data_axis, None, None),
                      P(dist.data_axis, None, None)),
            out_specs=P(tok_axes, None),
            check_vma=False,
        )(x, ids, weights, w_gate, w_up, w_down)
        return y, aux

    rs = dist.n_model > 1 and cfg.d_model % dist.n_model == 0
    body = functools.partial(_moe_local, cfg=cfg, n_data=n_data, e_pad=e_pad,
                             data_axis=dist.data_axis,
                             model_axis=dist.model_axis if dist.n_model > 1 else None,
                             rs_combine=rs)
    y = jax.shard_map(
        body, mesh=dist.mesh,
        in_specs=(P(batch, None), P(batch, None), P(batch, None),
                  P(dist.data_axis, None, dist.model_axis),
                  P(dist.data_axis, None, dist.model_axis),
                  P(dist.data_axis, dist.model_axis, None)),
        out_specs=P(batch, dist.model_axis if rs else None),
        check_vma=False,
    )(x, ids, weights, w_gate, w_up, w_down)
    return y, aux


def moe_apply(x: jax.Array, p: dict, cfg, dist: Dist | None
              ) -> tuple[jax.Array, jax.Array]:
    """Dispatch: EP under a real mesh, exact dense reference otherwise;
    plus the shared expert where the layer has one (``p["shared"]``).
    x may be (B, S, d) or (T, d); returns same leading shape."""
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    # token-parallel for small experts (MXU-aligned full-width FFNs)
    tp = (dist is not None and dist.n_model > 1
          and cfg.d_ff_expert < 128 * dist.n_model)
    tok_shards = dist.n_pod * dist.n_data if dist is not None else 1
    if tp:
        tok_shards *= dist.n_model
    if (dist is not None and dist.mesh is not None and dist.n_data > 1
            and x2.shape[0] % tok_shards == 0):
        y, aux = moe_ffn_ep(x2, p, cfg, dist, token_parallel=tp)
    else:
        # Tiny token counts (batch-1 long-context decode): every device
        # computes its expert shard for all tokens; GSPMD's einsum
        # partitioning handles it without routing buffers.
        y, aux = moe_ffn_dense_exact(x2, p, cfg)
    if "shared" in p:
        s = p["shared"]
        y = y + swiglu(x2, s["w_gate"], s["w_up"], s["w_down"]).astype(y.dtype)
    return y.reshape(shape), aux
