"""Unified decoder stack for all assigned families.

The stack is a ``lax.scan`` over *layer groups*: the smallest repeating
pattern of statically-typed sublayers (dense: 1 attn layer; gemma2:
[local, global]; jamba: 8-layer [mamba×4, attn, mamba×3] block with
alternating dense/MoE FFNs; falcon-mamba: 1 mamba layer). Group params are
stacked on a leading axis so HLO size is O(group), not O(depth) — a
95-layer deepseek compiles the same HLO as a 1-layer model.

Attention is internally q-chunked (``lax.scan`` over query blocks) so full
(Sq × Skv) logits never materialize: 32 k-token prefill peaks at
(B, H, q_chunk, Skv) per layer. Sliding windows are *static* per sublayer
(group unrolling makes gemma2's alternation static), letting local layers
slice their KV range instead of masking the full sequence.

Modes:
    train    — full sequence, no cache
    prefill  — writes the KV/SSM cache; optionally chunked at the model
               level (static chunk offsets; kimi-k2 memory)
    decode   — one token against the cache (kv_len-ragged)
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.distributed.context import Dist
from repro.models import attention as attn
from repro.models import mamba as mam
from repro.models import mamba2 as mam2
from repro.models import moe as moe_mod
from repro.models.layers import (dtype_of, init_attention, init_mamba,
                                 init_mlp, init_moe, rms_norm, swiglu)

BIG_WINDOW = 1 << 30


@dataclass(frozen=True)
class SubLayerSpec:
    kind: str                 # "attn" | "mamba"
    mlp: str                  # "dense" | "moe" | "none"
    window: int | None = None
    causal: bool = True
    cross: bool = False       # whisper decoder cross-attention


def layer_pattern(cfg) -> list[SubLayerSpec]:
    kinds = cfg.layer_kinds()
    mlps = cfg.mlp_kinds()
    return [SubLayerSpec(kind=kinds[i], mlp=mlps[i],
                         window=cfg.window_for_layer(i),
                         cross=(cfg.family == "encdec"))
            for i in range(cfg.n_layers)]


def layer_groups(cfg) -> tuple[list[SubLayerSpec], int]:
    """Minimal repeating group and its count."""
    pat = layer_pattern(cfg)
    L = len(pat)
    for p in range(1, L + 1):
        if L % p == 0 and all(pat[i] == pat[i % p] for i in range(L)):
            return pat[:p], L // p
    return pat, 1


# ---------------------------------------------------------------------------
# Parameter initialization (stacked over groups).
# ---------------------------------------------------------------------------

def init_sublayer(key, cfg, spec: SubLayerSpec) -> dict:
    dt = dtype_of(cfg)
    keys = jax.random.split(key, 4)
    d = cfg.d_model
    p: dict = {"ln_mix": jnp.zeros((d,), jnp.float32)}
    if spec.kind == "attn":
        p["mix"] = init_attention(keys[0], cfg)
    elif cfg.ssm_kind == "mamba2":
        p["mix"] = mam2.init_mamba2(keys[0], cfg)
    else:
        p["mix"] = init_mamba(keys[0], cfg)
    if spec.cross:
        p["ln_cross"] = jnp.zeros((d,), jnp.float32)
        p["cross"] = init_attention(keys[3], cfg)
    if spec.mlp == "dense":
        p["ln_mlp"] = jnp.zeros((d,), jnp.float32)
        p["mlp"] = init_mlp(keys[1], d, cfg.d_ff, dt)
    elif spec.mlp == "moe":
        p["ln_mlp"] = jnp.zeros((d,), jnp.float32)
        p["mlp"] = init_moe(keys[2], cfg)
    return p


def init_stack(key, cfg) -> dict:
    """Group-stacked params: leaf shapes (n_groups, ...). Built by a
    ``vmap`` over the group keys, so each leaf is created stacked: no
    per-group copies stay alive to be stacked (2x the params at peak), and
    a jitted init traces one group, not ``n_groups``."""
    group, n_groups = layer_groups(cfg)
    keys = jax.random.split(key, n_groups)

    def one_group(k):
        sub = jax.random.split(k, len(group))
        return {f"sub{i}": init_sublayer(sub[i], cfg, spec)
                for i, spec in enumerate(group)}

    return jax.vmap(one_group)(keys)


# ---------------------------------------------------------------------------
# Caches.
# ---------------------------------------------------------------------------

def init_cache(cfg, batch: int, max_len: int, dtype=None) -> dict:
    """Group-stacked cache pytree (zeros; kv_len tracks validity)."""
    dt = dtype or dtype_of(cfg)
    group, n_groups = layer_groups(cfg)

    def one(spec: SubLayerSpec) -> dict:
        if spec.kind == "attn":
            return {"k": jnp.zeros((batch, max_len, cfg.n_kv_heads,
                                    cfg.head_dim), dt),
                    "v": jnp.zeros((batch, max_len, cfg.n_kv_heads,
                                    cfg.head_dim), dt)}
        if cfg.ssm_kind == "mamba2":
            return mam2.init_mamba2_state(cfg, batch, dt)
        return mam.init_mamba_state(cfg, batch, dt)

    def stack(tree):
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x, (n_groups,) + x.shape), tree)

    return {f"sub{i}": stack(one(spec)) for i, spec in enumerate(group)}


# ---------------------------------------------------------------------------
# Attention sublayer (train / prefill / decode).
# ---------------------------------------------------------------------------

def _q_chunked_attend(q, k, v, *, causal, window, softcap, kv_offset,
                      q_chunk: int, scale=None):
    """Scan over query chunks so (Sq×Skv) logits never materialize."""
    B, Sq, H, dh = q.shape
    if Sq <= q_chunk:
        return attn.attend_prefill(q, k, v, causal=causal, window=window,
                                   softcap=softcap, kv_offset=kv_offset,
                                   scale=scale)
    if Sq % q_chunk:
        # largest divisor of Sq ≤ q_chunk (whisper's 1500-frame encoder)
        q_chunk = next(c for c in range(q_chunk, 0, -1) if Sq % c == 0)
    nc = Sq // q_chunk
    qs = q.reshape(B, nc, q_chunk, H, dh).swapaxes(0, 1)   # (nc,B,qc,H,dh)

    def body(_, inp):
        qc, i = inp
        out = attn.attend_prefill(qc, k, v, causal=causal, window=window,
                                  softcap=softcap,
                                  kv_offset=kv_offset + i * q_chunk,
                                  scale=scale)
        return None, out

    _, outs = jax.lax.scan(body, None,
                           (qs, jnp.arange(nc, dtype=jnp.int32)))
    return outs.swapaxes(0, 1).reshape(B, Sq, H, dh)


def _seq_shard(arr, dist, cfg):
    """Context parallelism fallback: when heads don't divide the model
    axis, shard the QUERY SEQUENCE over it instead (otherwise attention
    compute replicates 16× across model shards — §Perf A iteration 2)."""
    if (dist is None or dist.mesh is None
            or cfg.n_heads % max(1, dist.n_model) == 0
            or arr.shape[1] % max(1, dist.n_model) != 0):
        return arr
    return jax.lax.with_sharding_constraint(
        arr, dist.sharding(dist.batch_axes, dist.model_axis, None, None))


def attn_sublayer(x, sp, cfg, spec: SubLayerSpec, *, mode: str,
                  positions, cache=None, kv_len=None, kv_offset: int = 0,
                  q_chunk: int = 256, dist=None):
    """Returns (the mixer's output, to be added to x; new_cache)."""
    h = rms_norm(x, sp["ln_mix"], cfg.norm_eps)
    theta = cfg.rope_theta if cfg.family != "encdec" else None
    window = spec.window
    scale = cfg.attn_scale

    if mode == "decode":
        # x (B, 1, d); cache (B, S, Hkv, dh); write at kv_len, read ≤ kv_len.
        q, k, v = attn.qkv_project(h, sp["mix"], positions, theta)
        B = x.shape[0]
        bidx = jnp.arange(B)
        new_k = cache["k"].at[bidx, kv_len].set(k[:, 0].astype(cache["k"].dtype))
        new_v = cache["v"].at[bidx, kv_len].set(v[:, 0].astype(cache["v"].dtype))
        out = attn.attend_decode(q[:, 0], new_k, new_v, kv_len + 1,
                                 window=window, softcap=cfg.attn_softcap,
                                 scale=scale)
        out = attn.out_project(out, sp["mix"])[:, None, :]
        return out, {"k": new_k, "v": new_v}

    q, k, v = attn.qkv_project(h, sp["mix"], positions, theta)
    new_cache = cache
    if mode == "prefill":
        S = x.shape[1]
        new_k = jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (0, kv_offset, 0, 0))
        new_v = jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, kv_offset, 0, 0))
        new_cache = {"k": new_k, "v": new_v}
        if kv_offset > 0:
            # Chunked prefill: attend against everything cached so far.
            hist = kv_offset + S
            k_att = jax.lax.slice_in_dim(new_k, 0, hist, axis=1).astype(q.dtype)
            v_att = jax.lax.slice_in_dim(new_v, 0, hist, axis=1).astype(q.dtype)
            out = _q_chunked_attend(q, k_att, v_att, causal=spec.causal,
                                    window=window, softcap=cfg.attn_softcap,
                                    kv_offset=kv_offset, q_chunk=q_chunk,
                                    scale=scale)
            return attn.out_project(out, sp["mix"]), new_cache

    q = _seq_shard(q, dist, cfg)
    out = _q_chunked_attend(q, k, v, causal=spec.causal, window=window,
                            softcap=cfg.attn_softcap, kv_offset=0,
                            q_chunk=q_chunk, scale=scale)
    out = _seq_shard(out, dist, cfg)
    return attn.out_project(out, sp["mix"]), new_cache


def cross_sublayer(x, sp, cfg, enc_kv):
    """Whisper decoder cross-attention (enc K/V precomputed)."""
    h = rms_norm(x, sp["ln_cross"], cfg.norm_eps)
    q = jnp.einsum("bsd,dhk->bshk", h, sp["cross"]["wq"].astype(h.dtype))
    out = attn.attend_prefill(q, enc_kv["k"], enc_kv["v"], causal=False,
                              window=None, softcap=None)
    out = attn.out_project(out, sp["cross"])
    return x + out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Full sublayer + group application.
# ---------------------------------------------------------------------------

def sublayer_apply(x, sp, cfg, spec: SubLayerSpec, dist: Dist | None, *,
                   mode: str, positions, cache, kv_len, kv_offset,
                   enc_kv=None):
    """Returns (x, new_cache, aux)."""
    aux = jnp.zeros((), jnp.float32)

    def residual(x, out):
        if cfg.residual_multiplier != 1.0:
            out = out * cfg.residual_multiplier
        return x + out.astype(x.dtype)

    if spec.kind == "attn":
        out, new_cache = attn_sublayer(x, sp, cfg, spec, mode=mode,
                                       positions=positions, cache=cache,
                                       kv_len=kv_len, kv_offset=kv_offset,
                                       dist=dist)
    else:
        h = rms_norm(x, sp["ln_mix"], cfg.norm_eps)
        state = None if mode == "train" else cache
        if cfg.ssm_kind == "mamba2":
            out, new_cache = mam2.mamba2_block(h, sp["mix"], cfg, state=state,
                                               decode=mode == "decode")
        else:
            out, new_cache = mam.mamba_block(h, sp["mix"], cfg, state=state)
    x = residual(x, out)

    if spec.cross and enc_kv is not None:
        x = cross_sublayer(x, sp, cfg, enc_kv)

    if spec.mlp == "dense":
        h = rms_norm(x, sp["ln_mlp"], cfg.norm_eps)
        x = residual(x, swiglu(h, sp["mlp"]["w_gate"], sp["mlp"]["w_up"],
                               sp["mlp"]["w_down"]))
    elif spec.mlp == "moe":
        h = rms_norm(x, sp["ln_mlp"], cfg.norm_eps)
        y, aux = moe_mod.moe_apply(h, sp["mlp"], cfg, dist)
        x = residual(x, y)
    return x, new_cache, aux


def _constrain(x, dist: Dist | None):
    if dist is not None and dist.mesh is not None:
        x = jax.lax.with_sharding_constraint(
            x, dist.sharding(dist.batch_axes, None, None))
    return x


def stack_apply(x, stack_params, cfg, dist: Dist | None, *, mode: str,
                positions, cache=None, kv_len=None, kv_offset: int = 0,
                enc_kv=None, group=None):
    """Scan the group-stacked params over the input.

    Returns (x, new_cache, total_aux). ``cache``/new_cache are group-stacked
    pytrees (or None in train mode).
    """
    if group is None:
        group, _ = layer_groups(cfg)

    def group_body(x, gp, gcache, genc):
        new_caches = {}
        aux_total = jnp.zeros((), jnp.float32)
        for i, spec in enumerate(group):
            sub_cache = gcache[f"sub{i}"] if gcache is not None else None
            x, nc, aux = sublayer_apply(
                x, gp[f"sub{i}"], cfg, spec, dist, mode=mode,
                positions=positions, cache=sub_cache, kv_len=kv_len,
                kv_offset=kv_offset, enc_kv=genc)
            new_caches[f"sub{i}"] = nc
            aux_total = aux_total + aux
        x = _constrain(x, dist)
        return x, new_caches, aux_total

    if cfg.remat == "dots":
        group_body = jax.checkpoint(
            group_body, policy=jax.checkpoint_policies.checkpoint_dots)
    elif cfg.remat == "full":
        group_body = jax.checkpoint(group_body)

    xs = {"p": stack_params}
    if cache is not None:
        xs["c"] = cache
    if enc_kv is not None:
        xs["e"] = enc_kv                     # group-stacked cross K/V

    def scan_body(x, inp):
        x, new_cache, aux = group_body(x, inp["p"], inp.get("c"),
                                       inp.get("e"))
        return x, (new_cache if cache is not None else 0, aux)

    x, (new_cache, auxs) = jax.lax.scan(scan_body, x, xs)
    return x, (new_cache if cache is not None else None), jnp.sum(auxs)
