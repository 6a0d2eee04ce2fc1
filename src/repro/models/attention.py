"""GQA attention: prefill and decode paths.

Pure-jnp formulation (XLA fuses the softmax chain). The Pallas kernels
``repro.kernels.ops.flash_attention`` / ``decode_attention`` are not wired
in.

Supports: grouped KV heads, sliding-window + causal masks with absolute
positions (``kv_offset`` for chunked prefill), attention logit softcap
(gemma2), a configured score scale (granite's ``attention_multiplier``;
default head_dim ** -0.5), ragged decode lengths.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import apply_rope

NEG_INF = -1e30


def qkv_project(x: jax.Array, p: dict, positions: jax.Array,
                rope_theta: float | None) -> tuple[jax.Array, jax.Array, jax.Array]:
    """x (B, S, d) → q (B, S, H, dh), k/v (B, S, Hkv, dh), roped."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(x.dtype))
    if rope_theta is not None:
        q = apply_rope(q, positions, rope_theta)
        k = apply_rope(k, positions, rope_theta)
    return q, k, v


def attend_prefill(q: jax.Array, k: jax.Array, v: jax.Array, *,
                   causal: bool = True, window: int | None = None,
                   softcap: float | None = None, kv_offset: int | jax.Array = 0,
                   kv_chunk: int = 1024, scale: float | None = None
                   ) -> jax.Array:
    """q (B, Sq, H, dh); k/v (B, Skv, Hkv, dh) → (B, Sq, H, dh).

    Online-softmax over KV chunks (flash structure in jnp): logits exist
    only as (B, Hkv, g, Sq, kv_chunk) tiles inside the scan, never at
    (…, Sq, Skv) scale — the XLA-space analogue of
    ``kernels/flash_attention`` (§Perf A iteration 1).

    Query i sits at absolute position i + kv_offset; kv j at position j.
    """
    B, Sq, H, dh = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    g = H // Hkv
    qg = q.reshape(B, Sq, Hkv, g, dh).astype(jnp.float32) * (
        dh ** -0.5 if scale is None else scale)
    qpos = jnp.arange(Sq)[:, None] + kv_offset                 # (Sq, 1)

    kc = min(kv_chunk, Skv)
    if Skv % kc:
        kc = Skv  # irregular sizes (whisper 1500): single chunk
    nk = Skv // kc
    ks = k.reshape(B, nk, kc, Hkv, dh).swapaxes(0, 1)
    vs = v.reshape(B, nk, kc, Hkv, dh).swapaxes(0, 1)

    def body(carry, inp):
        m, l, acc = carry
        kcnk, vcnk, j = inp
        logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg,
                            kcnk.astype(jnp.float32))
        if softcap is not None:
            logits = softcap * jnp.tanh(logits / softcap)
        kpos = j * kc + jnp.arange(kc)[None, :]                # (1, kc)
        mask = jnp.ones((Sq, kc), bool)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        logits = jnp.where(mask[None, None, None], logits, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        p = jnp.exp(logits - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = alpha * l + jnp.sum(p, axis=-1)
        # bf16 probabilities (max error ~4e-3 on p∈[0,1]), fp32 accumulate —
        # halves the dominant tile traffic (§Perf B iteration 1).
        acc = alpha[..., None] * acc + jnp.einsum(
            "bhgqk,bkhd->bhgqd", p.astype(jnp.bfloat16), vcnk,
            preferred_element_type=jnp.float32)
        return (m_new, l, acc), None

    m0 = jnp.full((B, Hkv, g, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hkv, g, Sq), jnp.float32)
    acc0 = jnp.zeros((B, Hkv, g, Sq, dh), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, acc0),
        (ks, vs, jnp.arange(nk, dtype=jnp.int32)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]               # (B,Hkv,g,Sq,dh)
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, dh)
    return out.astype(q.dtype)


def attend_prefill_dynwin(q, k, v, *, window: jax.Array,
                          softcap: float | None = None,
                          kv_offset: int | jax.Array = 0) -> jax.Array:
    """Like attend_prefill but ``window`` is a traced scalar (gemma2's
    alternating local/global layers inside one scanned stack: window is a
    per-layer value; a huge window ≡ global attention)."""
    B, Sq, H, dh = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    g = H // Hkv
    qg = q.reshape(B, Sq, Hkv, g, dh)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * (dh ** -0.5)
    if softcap is not None:
        logits = softcap * jnp.tanh(logits / softcap)
    qpos = jnp.arange(Sq)[:, None] + kv_offset
    kpos = jnp.arange(Skv)[None, :]
    mask = (kpos <= qpos) & (kpos > qpos - window)
    logits = jnp.where(mask[None, None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v.astype(jnp.float32))
    return out.reshape(B, Sq, H, dh).astype(q.dtype)


def attend_decode(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                  kv_len: jax.Array, *, window: int | jax.Array | None = None,
                  softcap: float | None = None,
                  scale: float | None = None) -> jax.Array:
    """One-token decode. q (B, H, dh); caches (B, S, Hkv, dh); kv_len (B,).

    The new token sits at absolute position kv_len − 1 (already appended).

    NOTE (§Perf E, refuted): slicing the cache read to the sliding window
    (gemma2 local layers: 4 k of 32 k) was tried and made the cell 6×
    WORSE — the per-row dynamic_slice fights the KV **sequence** sharding
    (kv_heads < model axis ⇒ seq@model), forcing GSPMD to all-gather the
    whole cache (collective 0.78 ms → 699 ms). The masked full read below
    is optimal under this layout; window slicing needs a ring-buffer /
    paged-KV layout instead (future work, `kernels/decode_attention`
    handles it with ragged kv_len on real TPU).
    """
    B, H, dh = q.shape
    S, Hkv = k_cache.shape[1], k_cache.shape[2]
    g = H // Hkv
    qg = q.reshape(B, Hkv, g, dh)
    logits = jnp.einsum("bhgd,bkhd->bhgk", qg.astype(jnp.float32),
                        k_cache.astype(jnp.float32)) * (
        dh ** -0.5 if scale is None else scale)
    if softcap is not None:
        logits = softcap * jnp.tanh(logits / softcap)
    kpos = jnp.arange(S)[None, :]
    mask = kpos < kv_len[:, None]                              # (B, S)
    if window is not None:
        mask &= kpos > (kv_len[:, None] - 1) - window
    logits = jnp.where(mask[:, None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgk,bkhd->bhgd", probs, v_cache.astype(jnp.float32))
    return out.reshape(B, H, dh).astype(q.dtype)


def out_project(attn: jax.Array, p: dict) -> jax.Array:
    """attn (..., H, dh) @ wo (H, dh, d) → (..., d)."""
    return jnp.einsum("...hk,hkd->...d", attn, p["wo"].astype(attn.dtype))
