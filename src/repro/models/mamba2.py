"""Mamba-2 mixer (granite-4.0-h's SSM layers): SSD [Dao & Gu, arXiv:2405.21060].

One layer: ``w_in`` projects to [z (di), xBC (di + 2·G·N), dt (H)]; a
depthwise causal conv (K taps, with bias) and SiLU run over xBC, which
splits into x (H heads of P channels), B and C (G groups of N, each group
shared by H/G heads); dt = softplus(dt + dt_bias), A = −exp(A_log) per
head. The scan, per head,

    h_t = exp(dt_t·A)·h_{t−1} + dt_t · x_t ⊗ B_t        (P × N)
    y_t = C_t · h_t + D·x_t

is followed by the gated RMSNorm norm(y · silu(z)) over all di channels
and ``w_out``.

Prefill/train run the chunked SSD form: within a chunk of Q positions
the quadratic form (decays exp(Σ dt·A) between positions, masked
causally), with the state carried from chunk to chunk by ``lax.scan``.
Decode runs the one-step recurrence. The carried state is
{"h": (B, H, P, N) in ``STATE_DTYPE``, "conv": (B, K−1, conv_dim)}, the
conv window holding the last K−1 raw xBC inputs. The state is float32:
the recurrence accumulates over every position.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers import _normal, dtype_of, rms_norm

STATE_DTYPE = jnp.float32


def init_mamba2(key, cfg) -> dict:
    dt = dtype_of(cfg)
    d, di, nh = cfg.d_model, cfg.ssm_d_inner, cfg.ssm_n_heads
    cd, K = cfg.ssm_conv_dim, cfg.ssm_d_conv
    k = jax.random.split(key, 5)
    step = jnp.exp(jax.random.uniform(k[3], (nh,), jnp.float32,
                                      minval=np.log(1e-3),
                                      maxval=np.log(1e-1)))
    return {
        "w_in": _normal(k[0], (d, di + cd + nh), dt, d ** -0.5),  # [z,xBC,dt]
        "conv_w": _normal(k[1], (K, cd), dt, K ** -0.5),
        "conv_b": jnp.zeros((cd,), dt),
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),       # softplus⁻¹
        "A_log": jnp.log(jax.random.uniform(k[4], (nh,), jnp.float32,
                                            minval=1.0, maxval=16.0)),
        "D": jnp.ones((nh,), jnp.float32),
        "norm": jnp.zeros((di,), jnp.float32),
        "w_out": _normal(k[2], (di, d), dt, di ** -0.5),
    }


def init_mamba2_state(cfg, batch: int, dtype=jnp.bfloat16) -> dict:
    return {"h": jnp.zeros((batch, cfg.ssm_n_heads, cfg.ssm_head_dim,
                            cfg.ssm_d_state), STATE_DTYPE),
            "conv": jnp.zeros((batch, cfg.ssm_d_conv - 1, cfg.ssm_conv_dim),
                              dtype)}


def _split(xbc: jax.Array, cfg):
    """(..., conv_dim) → x (..., G, H/G, P), B and C (..., G, N)."""
    di, G, N = cfg.ssm_d_inner, cfg.ssm_n_groups, cfg.ssm_d_state
    lead = xbc.shape[:-1]
    x = xbc[..., :di].reshape(*lead, G, cfg.ssm_n_heads // G,
                              cfg.ssm_head_dim)
    Bm = xbc[..., di:di + G * N].reshape(*lead, G, N)
    Cm = xbc[..., di + G * N:].reshape(*lead, G, N)
    return x, Bm, Cm


def _grouped(v: jax.Array, cfg) -> jax.Array:
    """A per-head vector (..., H) as (..., G, H/G)."""
    G = cfg.ssm_n_groups
    return v.reshape(*v.shape[:-1], G, v.shape[-1] // G)


def ssd_chunked(x, dt, A, Bm, Cm, h0, chunk: int):
    """The SSD scan in chunks. x (B, L, G, Hg, P), dt (B, L, G, Hg)
    (after softplus), A (G, Hg), Bm/Cm (B, L, G, N), h0 (B, G, Hg, P, N),
    all float32. Returns y (B, L, G, Hg, P) without the D skip, and the
    state after position L − 1. A tail shorter than ``chunk`` is padded
    with dt = 0, which leaves the state as it is."""
    Bsz, L = x.shape[:2]
    Q = min(chunk, L)
    pad = -L % Q
    if pad:
        def padded(a):
            return jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
        x, dt, Bm, Cm = map(padded, (x, dt, Bm, Cm))
    nc = (L + pad) // Q

    def chunks(a):                      # (B, L, ...) → (nc, B, Q, ...)
        return a.reshape(Bsz, nc, Q, *a.shape[2:]).swapaxes(0, 1)

    causal = jnp.tril(jnp.ones((Q, Q), bool))[None, :, :, None, None]

    def body(h, inp):
        xc, dtc, Bc, Cc = inp
        acum = jnp.cumsum(dtc * A, axis=1)                  # (B, Q, G, Hg)
        seg = acum[:, :, None] - acum[:, None, :]           # (B, Qi, Qj, G, Hg)
        decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
        u = xc * dtc[..., None]                             # dt_j · x_j
        cb = jnp.einsum("bign,bjgn->bijg", Cc, Bc)
        y = jnp.einsum("bijgh,bjghp->bighp", decay * cb[..., None], u)
        y = y + jnp.einsum("bign,bghpn->bighp", Cc, h) \
            * jnp.exp(acum)[..., None]
        to_end = jnp.exp(acum[:, -1:] - acum)               # (B, Q, G, Hg)
        h = h * jnp.exp(acum[:, -1])[..., None, None] + jnp.einsum(
            "bjgn,bjghp->bghpn", Bc, u * to_end[..., None])
        return h, y

    h, ys = jax.lax.scan(body, h0, tuple(map(chunks, (x, dt, Bm, Cm))))
    y = ys.swapaxes(0, 1).reshape(Bsz, nc * Q, *x.shape[2:])
    return y[:, :L], h


def ssd_step(x, dt, A, Bm, Cm, h):
    """One position of the recurrence. x (B, G, Hg, P), dt (B, G, Hg),
    Bm/Cm (B, G, N), h (B, G, Hg, P, N), float32. Returns (y, h)."""
    h = jnp.exp(dt * A)[..., None, None] * h \
        + (x * dt[..., None])[..., None] * Bm[:, :, None, None, :]
    return jnp.einsum("bghpn,bgn->bghp", h, Cm), h


def _conv(xbc: jax.Array, p: dict, window: jax.Array) -> jax.Array:
    """Depthwise causal conv + SiLU in float32. xbc (B, L, cd) after the
    K−1 inputs in ``window`` (B, K−1, cd)."""
    w = p["conv_w"].astype(jnp.float32)
    K, L = w.shape[0], xbc.shape[1]
    xp = jnp.concatenate([window, xbc], axis=1).astype(jnp.float32)
    out = sum(xp[:, i:i + L] * w[i] for i in range(K))
    return jax.nn.silu(out + p["conv_b"].astype(jnp.float32))


def mamba2_block(x: jax.Array, p: dict, cfg, state: dict | None = None,
                 decode: bool = False):
    """x (B, L, d) → (B, L, d), new state. ``state`` None starts from
    zeros (train); ``decode`` takes the one-step recurrence (L = 1)."""
    B, L, _ = x.shape
    di, cd, K = cfg.ssm_d_inner, cfg.ssm_conv_dim, cfg.ssm_d_conv
    zxd = jnp.einsum("bld,dk->blk", x, p["w_in"].astype(x.dtype))
    z, xbc, dt = zxd[..., :di], zxd[..., di:di + cd], zxd[..., di + cd:]
    if state is None:
        state = init_mamba2_state(cfg, B, x.dtype)
    window = state["conv"].astype(xbc.dtype)
    xs, Bm, Cm = _split(_conv(xbc, p, window), cfg)
    dt = _grouped(jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"]), cfg)
    A = _grouped(-jnp.exp(p["A_log"].astype(jnp.float32)), cfg)
    h0 = state["h"].astype(jnp.float32).reshape(
        B, cfg.ssm_n_groups, -1, cfg.ssm_head_dim, cfg.ssm_d_state)
    if decode:
        y, h = ssd_step(xs[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0], h0)
        y = y[:, None]
    else:
        y, h = ssd_chunked(xs, dt, A, Bm, Cm, h0, cfg.ssm_chunk)
    y = y + _grouped(p["D"], cfg)[..., None] * xs
    y = y.reshape(B, L, di) * jax.nn.silu(z.astype(jnp.float32))
    y = rms_norm(y, p["norm"], cfg.norm_eps).astype(x.dtype)
    out = jnp.einsum("blk,kd->bld", y, p["w_out"].astype(x.dtype))
    new_state = {
        "h": h.reshape(state["h"].shape).astype(state["h"].dtype),
        "conv": jnp.concatenate([window, xbc], axis=1)[:, -(K - 1):]
        .astype(state["conv"].dtype)}
    return out, new_state
