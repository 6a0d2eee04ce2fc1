"""granite-moe-3b-a800m: the weights from the seed, the plain reference
forward, and the FLOPs one request requires.

The reference follows the decoder the configuration states (pre-norm
RMSNorm with (1 + scale), rotary embeddings on split halves, grouped-query
causal attention, a softmax router whose top-k weights are renormalized,
SwiGLU experts, an untied LM head), written out in float32 at
``Precision.HIGHEST`` layer by layer, with no cache and no batching
tricks: every position attends to every earlier one. It imports nothing
of the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def padded_vocab(v: int, unit: int = 2048) -> int:
    return ((v + unit - 1) // unit) * unit


def make_weights(a: dict, key) -> dict:
    """Random weights in the served dtype (bf16; router and norms fp32),
    in the layout the serving program takes: every layer's tensors
    stacked on a leading axis. Call it under ``jax.jit``."""
    d, L, E = a["d_model"], a["n_layers"], a["n_experts"]
    H, Hkv, dh, ff = a["n_heads"], a["n_kv_heads"], a["head_dim"], \
        a["d_ff_expert"]
    vp = padded_vocab(a["vocab_size"])
    bf = jnp.bfloat16
    ks = iter(jax.random.split(key, 16))

    def normal(shape, scale, dtype=bf):
        return (jax.random.normal(next(ks), shape, jnp.float32)
                * scale).astype(dtype)

    layer = {
        "ln_mix": jnp.zeros((L, d), jnp.float32),
        "mix": {"wq": normal((L, d, H, dh), d ** -0.5),
                "wk": normal((L, d, Hkv, dh), d ** -0.5),
                "wv": normal((L, d, Hkv, dh), d ** -0.5),
                "wo": normal((L, H, dh, d), (H * dh) ** -0.5)},
        "ln_mlp": jnp.zeros((L, d), jnp.float32),
        "mlp": {"router": normal((L, d, E), d ** -0.5, jnp.float32),
                "w_gate": normal((L, E, d, ff), d ** -0.5),
                "w_up": normal((L, E, d, ff), d ** -0.5),
                "w_down": normal((L, E, ff, d), ff ** -0.5)},
    }
    return {"embed": normal((vp, d), d ** -0.5),
            "head": normal((vp, d), d ** -0.5),
            "final_norm": jnp.zeros((d,), jnp.float32),
            "stack": {"sub0": layer}}


# ------------------------------------------------------------- reference

def _mm(x, w, q):
    return jnp.matmul(q(x), q(w), precision=HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + scale)


def _rope(x, theta):
    """x (R, T, heads, dh): rotate (first half, second half) pairs by
    position · theta^(-2i/dh)."""
    T, dh = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv      # (T, dh/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(a: dict, q, x, p):
    """One decoder layer on x (R, T, d) fp32."""
    f32 = jnp.float32
    R, T, d = x.shape
    H, Hkv, dh = a["n_heads"], a["n_kv_heads"], a["head_dim"]
    eps = a["norm_eps"]
    h = _rms(x, p["ln_mix"], eps)
    wq = p["mix"]["wq"].astype(f32).reshape(d, H * dh)
    wk = p["mix"]["wk"].astype(f32).reshape(d, Hkv * dh)
    wv = p["mix"]["wv"].astype(f32).reshape(d, Hkv * dh)
    qh = _rope(_mm(h, wq, q).reshape(R, T, H, dh), a["rope_theta"])
    kh = _rope(_mm(h, wk, q).reshape(R, T, Hkv, dh), a["rope_theta"])
    vh = _mm(h, wv, q).reshape(R, T, Hkv, dh)
    g = H // Hkv
    kh = jnp.repeat(kh, g, axis=2)            # head j reads kv head j // g
    vh = jnp.repeat(vh, g, axis=2)
    s = jnp.einsum("rqhk,rshk->rhqs", q(qh), q(kh), precision=HIGHEST) \
        * dh ** -0.5
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    pr = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("rhqs,rshk->rqhk", q(pr), q(vh), precision=HIGHEST)
    x = x + _mm(o.reshape(R, T, H * dh),
                p["mix"]["wo"].astype(f32).reshape(H * dh, d), q)

    h = _rms(x, p["ln_mlp"], eps).reshape(R * T, d)
    m = p["mlp"]
    probs = jax.nn.softmax(_mm(h, m["router"].astype(f32), q), axis=-1)
    top_w, top_i = jax.lax.top_k(probs, a["moe_top_k"])
    top_w = top_w / jnp.sum(top_w, -1, keepdims=True)
    comb = jnp.zeros_like(probs).at[
        jnp.arange(R * T)[:, None], top_i].set(top_w)         # (RT, E)
    gt = jnp.einsum("td,edf->etf", q(h), q(m["w_gate"].astype(f32)),
                    precision=HIGHEST)
    up = jnp.einsum("td,edf->etf", q(h), q(m["w_up"].astype(f32)),
                    precision=HIGHEST)
    y = jnp.einsum("etf,efd->etd", q(jax.nn.silu(gt) * up),
                   q(m["w_down"].astype(f32)), precision=HIGHEST)
    y = jnp.einsum("etd,te->td", y, comb, precision=HIGHEST)
    return x + y.reshape(R, T, d)


def _fp8(x):
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def _same(x):
    return x


def reference_logits(a: dict, params: dict, seqs: np.ndarray,
                     first_pos: int, low_precision: bool = False):
    """Logits (R, T - first_pos, V) of the real vocabulary at positions
    first_pos..T-1 of ``seqs`` (R, T): a full causal forward, layer by
    layer. ``low_precision`` computes every matmul on fp8 (e4m3)
    operands: the control."""
    q = _fp8 if low_precision else _same
    f32 = jnp.float32
    layer_fn = jax.jit(lambda x, p: _layer(a, q, x, p))
    stack = params["stack"]["sub0"]
    x = jnp.take(params["embed"], jnp.asarray(seqs), axis=0).astype(f32)
    for i in range(a["n_layers"]):
        x = layer_fn(x, jax.tree.map(lambda t: t[i], stack))
    x = _rms(x[:, first_pos:], params["final_norm"], a["norm_eps"])
    V = a["vocab_size"]
    head = params["head"][:V].astype(f32)
    return jax.jit(lambda x, w: jnp.einsum(
        "rtd,vd->rtv", q(x), q(w), precision=HIGHEST))(x, head)


def served_token_gaps(a: dict, key, seqs: np.ndarray, served: np.ndarray,
                      first_pos: int, control: bool = False) -> dict:
    """Gap by which each served token's reference logit lies below the
    reference's best at its position (R, new). With ``control``, also the
    gap of the token that the fp8 computation puts first."""
    params = jax.jit(lambda k: make_weights(a, k))(key)
    ref = reference_logits(a, params, seqs, first_pos)
    best = jnp.max(ref, axis=-1)
    got = jnp.take_along_axis(ref, jnp.asarray(served)[..., None], -1)[..., 0]
    out = {"served": np.asarray(best - got)}
    if control:
        low = reference_logits(a, params, seqs, first_pos, low_precision=True)
        pick = jnp.argmax(low, axis=-1)
        got = jnp.take_along_axis(ref, pick[..., None], -1)[..., 0]
        out["control"] = np.asarray(best - got)
    return out


# ----------------------------------------------------------------- FLOPs

def request_flops(a: dict, prompt_len: int, new_tokens: int) -> float:
    """Forward FLOPs one request requires: its prompt tokens and
    ``new_tokens - 1`` decode tokens (the first new token comes from the
    prompt's last position), each through every layer with the top-k
    routed experts only and causal attention over its own context, and
    the LM head at the ``new_tokens`` positions whose logits pick a
    token. Padding and experts computed needlessly do not count."""
    d, L = a["d_model"], a["n_layers"]
    H, Hkv, dh = a["n_heads"], a["n_kv_heads"], a["head_dim"]
    E, k, ff, V = a["n_experts"], a["moe_top_k"], a["d_ff_expert"], \
        a["vocab_size"]
    per_token = (2 * d * (H + 2 * Hkv) * dh     # q, k, v projections
                 + 2 * H * dh * d               # output projection
                 + 2 * d * E                    # router
                 + k * 3 * 2 * d * ff)          # routed SwiGLU experts
    T = prompt_len + new_tokens - 1
    ctx = T * (T + 1) // 2                      # Σ attended positions
    return float(L * (T * per_token + 4 * H * dh * ctx)
                 + new_tokens * 2 * d * V)
