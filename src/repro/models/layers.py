"""Shared layers: norms, embeddings, rotary, MLP, parameter init.

Parameters are plain nested dicts of jnp arrays (no flax): stacked along a
leading layer axis for ``lax.scan``. Initializers take an explicit PRNG key
and return fp32 masters cast to the config dtype by the optimizer/trainer.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def dtype_of(cfg) -> jnp.dtype:
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# Norms (computed in fp32 regardless of activation dtype).
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings.
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x (..., S, H, dh); positions (..., S) int32."""
    dh = x.shape[-1]
    freqs = rope_frequencies(dh, theta)                       # (dh/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, dh/2)
    cos = jnp.cos(angles)[..., None, :]                        # (..., S, 1, dh/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoid_positions(length: int, dim: int) -> jax.Array:
    """Whisper-style absolute sinusoidal embeddings (fp32)."""
    pos = np.arange(length)[:, None]
    i = np.arange(dim // 2)[None, :]
    angle = pos / np.power(10000.0, 2 * i / dim)
    emb = np.concatenate([np.sin(angle), np.cos(angle)], axis=1)
    return jnp.asarray(emb, jnp.float32)


# ---------------------------------------------------------------------------
# MLP (SwiGLU).
# ---------------------------------------------------------------------------

def swiglu(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
           w_down: jax.Array) -> jax.Array:
    g = jnp.einsum("...d,df->...f", x, w_gate.astype(x.dtype))
    u = jnp.einsum("...d,df->...f", x, w_up.astype(x.dtype))
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    return jnp.einsum("...f,fd->...d", h, w_down.astype(x.dtype))


# ---------------------------------------------------------------------------
# Initializers.
# ---------------------------------------------------------------------------

def _normal(key, shape, dtype, scale):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def init_mlp(key, d_model: int, d_ff: int, dtype) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    s_in = d_model ** -0.5
    s_out = d_ff ** -0.5
    return {
        "w_gate": _normal(k1, (d_model, d_ff), dtype, s_in),
        "w_up": _normal(k2, (d_model, d_ff), dtype, s_in),
        "w_down": _normal(k3, (d_ff, d_model), dtype, s_out),
    }


def init_attention(key, cfg) -> dict:
    dt = dtype_of(cfg)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    d, dh = cfg.d_model, cfg.head_dim
    s = d ** -0.5
    return {
        "wq": _normal(k1, (d, cfg.n_heads, dh), dt, s),
        "wk": _normal(k2, (d, cfg.n_kv_heads, dh), dt, s),
        "wv": _normal(k3, (d, cfg.n_kv_heads, dh), dt, s),
        "wo": _normal(k4, (cfg.n_heads, dh, d), dt, (cfg.n_heads * dh) ** -0.5),
    }


def init_mamba(key, cfg) -> dict:
    dt = dtype_of(cfg)
    d, di, ns = cfg.d_model, cfg.ssm_d_inner, cfg.ssm_d_state
    dt_rank = max(1, d // 16)
    keys = jax.random.split(key, 7)
    A = -jnp.exp(jax.random.uniform(keys[5], (di, ns), jnp.float32,
                                    minval=np.log(0.5), maxval=np.log(16.0)))
    return {
        "w_in": _normal(keys[0], (d, 2 * di), dt, d ** -0.5),       # [x, z]
        "conv_w": _normal(keys[1], (cfg.ssm_d_conv, di), dt, 0.2),
        "conv_b": jnp.zeros((di,), dt),
        "w_x_proj": _normal(keys[2], (di, dt_rank + 2 * ns), dt, di ** -0.5),
        "w_dt": _normal(keys[3], (dt_rank, di), dt, dt_rank ** -0.5),
        "dt_bias": jnp.log(jnp.expm1(
            jnp.clip(jax.random.uniform(keys[4], (di,), jnp.float32,
                                        minval=1e-3, maxval=1e-1), 1e-4, None))),
        "A_log": jnp.log(-A),                                        # fp32
        "D": jnp.ones((di,), jnp.float32),
        "w_out": _normal(keys[6], (di, d), dt, di ** -0.5),
    }


def init_moe(key, cfg) -> dict:
    """Router over all n_experts; weights of the held experts only; the
    shared expert where ``d_ff_shared`` is set."""
    dt = dtype_of(cfg)
    d, ffe = cfg.d_model, cfg.d_ff_expert
    E, held = cfg.n_experts, cfg.n_experts_held
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s_in, s_out = d ** -0.5, ffe ** -0.5
    p = {
        "router": _normal(k1, (d, E), jnp.float32, s_in),
        "w_gate": _normal(k2, (held, d, ffe), dt, s_in),
        "w_up": _normal(k3, (held, d, ffe), dt, s_in),
        "w_down": _normal(k4, (held, ffe, d), dt, s_out),
    }
    if cfg.d_ff_shared:
        p["shared"] = init_mlp(jax.random.fold_in(key, 4), d,
                               cfg.d_ff_shared, dt)
    return p
