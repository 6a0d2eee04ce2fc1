"""granite-4.0-h-small: the weights from the seed, the plain reference
forward, and the FLOPs one request requires.

The reference follows the published block (hf ``granitemoehybrid``,
Mamba-2 after Dao & Gu, arXiv:2405.21060), written out in float32 at
``Precision.HIGHEST`` layer by layer, with no cache, no chunking and no
batching tricks:

- the input embedding times ``embedding_multiplier``; pre-norm RMSNorm
  before each mixer and each MoE; both residual branches times
  ``residual_multiplier``; a final RMSNorm, the tied embedding as the LM
  head, logits divided by ``logits_scaling``;
- Mamba-2 mixers as the token-by-token recurrence over the whole sequence:
  in_proj to [z, xBC, dt], a depthwise causal conv with bias and SiLU over
  xBC, dt = softplus(dt + dt_bias) (no clamp), A = −exp(A_log) per head,
  h ← exp(dt·A)·h + dt·x⊗B, y = C·h + D·x, then RMSNorm(y·silu(z)) over
  all d_inner channels and out_proj;
- attention with no positional encoding: grouped-query, causal, scores
  scaled by ``attn_scale`` (``attention_multiplier``);
- an MoE on every layer: a softmax router over all ``n_experts`` whose
  top-k weights are renormalized (the same as a softmax over the top-k
  logits), SwiGLU experts, plus a SwiGLU shared expert.

Departures from the published model, each shared with the program:

- this chip's share: the experts ``[experts_first, experts_first +
  experts_held)`` of the router's ``n_experts``; routes to other experts
  add nothing (their shard lies on other chips of the deployment);
- depth: the first ``n_layers`` layers (one period of ten);
- RMSNorm scales are stored as (1 + scale), zeros at init, as in the
  program; the published model stores the product;
- random weights from the seed. Their scales: each projection at
  1/sqrt(fan-in); the embedding at 1/8 of that, so that with the ×12
  multiplier and the tied head a token's own row does not decide its
  next token (at 1/sqrt(d) it outweighs the ten layers' output by 15
  standard deviations of the logits and every prompt decodes to a run of
  its last token); A from U[1, 16], dt from log-U[1e-3, 1e-1] through the
  inverse softplus, D = 1 (Mamba-2's initialization).

It imports nothing of the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
EMBED_SCALE = 0.125          # embedding std, relative to 1/sqrt(d_model)


def padded_vocab(v: int, unit: int = 2048) -> int:
    return ((v + unit - 1) // unit) * unit


def _kinds(a: dict) -> list[str]:
    return ["attn" if i % a["hybrid_period"] == a["hybrid_attn_index"]
            else "mamba" for i in range(a["n_layers"])]


def _ssm_dims(a: dict) -> tuple[int, int, int, int, int, int, int]:
    """d_inner, heads, head dim, groups, d_state, conv taps, conv width."""
    di = a["ssm_expand"] * a["d_model"]
    P, G, N = a["ssm_head_dim"], a["ssm_n_groups"], a["ssm_d_state"]
    return di, di // P, P, G, N, a["ssm_d_conv"], di + 2 * G * N


def make_weights(a: dict, key) -> dict:
    """Random weights in the served dtype (bf16; router, norms and the
    SSM's dt_bias, A_log and D fp32), in the layout the serving program
    takes: sublayer ``sub<i>`` of the ten-layer period, its tensors
    stacked on a leading axis over the periods. Call under ``jax.jit``."""
    d, L = a["d_model"], a["n_layers"]
    period = a["hybrid_period"]
    n_groups = L // period
    H, Hkv, dh = a["n_heads"], a["n_kv_heads"], a["head_dim"]
    E, held = a["n_experts"], a["experts_held"] or a["n_experts"]
    ff, ffs = a["d_ff_expert"], a["d_ff_shared"]
    di, nh, P, G, N, K, cd = _ssm_dims(a)
    vp = padded_vocab(a["vocab_size"])
    bf, f32 = jnp.bfloat16, jnp.float32
    ks = iter(jax.random.split(key, 32 * period + 4))

    def normal(shape, scale, dtype=bf):
        return (jax.random.normal(next(ks), (n_groups,) + shape, f32)
                * scale).astype(dtype)

    def uniform(shape, lo, hi):
        return jax.random.uniform(next(ks), (n_groups,) + shape, f32, lo, hi)

    def mamba():
        step = jnp.exp(uniform((nh,), np.log(1e-3), np.log(1e-1)))
        return {"w_in": normal((d, di + cd + nh), d ** -0.5),
                "conv_w": normal((K, cd), K ** -0.5),
                "conv_b": normal((cd,), 0.1),
                "dt_bias": step + jnp.log(-jnp.expm1(-step)),
                "A_log": jnp.log(uniform((nh,), 1.0, 16.0)),
                "D": jnp.ones((n_groups, nh), f32),
                "norm": jnp.zeros((n_groups, di), f32),
                "w_out": normal((di, d), di ** -0.5)}

    def attention():
        return {"wq": normal((d, H, dh), d ** -0.5),
                "wk": normal((d, Hkv, dh), d ** -0.5),
                "wv": normal((d, Hkv, dh), d ** -0.5),
                "wo": normal((H, dh, d), (H * dh) ** -0.5)}

    def moe():
        return {"router": normal((d, E), d ** -0.5, f32),
                "w_gate": normal((held, d, ff), d ** -0.5),
                "w_up": normal((held, d, ff), d ** -0.5),
                "w_down": normal((held, ff, d), ff ** -0.5),
                "shared": {"w_gate": normal((d, ffs), d ** -0.5),
                           "w_up": normal((d, ffs), d ** -0.5),
                           "w_down": normal((ffs, d), ffs ** -0.5)}}

    stack = {}
    for i, kind in enumerate(_kinds(a)[:period]):
        stack[f"sub{i}"] = {
            "ln_mix": jnp.zeros((n_groups, d), f32),
            "mix": attention() if kind == "attn" else mamba(),
            "ln_mlp": jnp.zeros((n_groups, d), f32),
            "mlp": moe()}
    embed = (jax.random.normal(next(ks), (vp, d), f32)
             * (EMBED_SCALE * d ** -0.5)).astype(bf)
    return {"embed": embed, "final_norm": jnp.zeros((d,), f32),
            "stack": stack}


# ------------------------------------------------------------- reference

def _mm(x, w, q):
    return jnp.matmul(q(x), q(w), precision=HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + scale)


def _swiglu(h, w_gate, w_up, w_down, q):
    f32 = jnp.float32
    g = _mm(h, w_gate.astype(f32), q)
    u = _mm(h, w_up.astype(f32), q)
    return _mm(jax.nn.silu(g) * u, w_down.astype(f32), q)


def _mamba2(a: dict, q, h, p):
    """The Mamba-2 mixer on h (R, T, d) fp32 (already normed), token by
    token from a zero state."""
    f32 = jnp.float32
    R, T, d = h.shape
    di, nh, P, G, N, K, cd = _ssm_dims(a)
    zxd = _mm(h, p["w_in"].astype(f32), q)
    z, xbc, dt = zxd[..., :di], zxd[..., di:di + cd], zxd[..., di + cd:]
    w = p["conv_w"].astype(f32)                                # (K, cd)
    xp = jnp.concatenate([jnp.zeros((R, K - 1, cd), f32), xbc], axis=1)
    xbc = jax.nn.silu(sum(xp[:, k:k + T] * w[k] for k in range(K))
                      + p["conv_b"].astype(f32))
    x = xbc[..., :di].reshape(R, T, nh, P)
    Bm = jnp.repeat(xbc[..., di:di + G * N].reshape(R, T, G, N),
                    nh // G, axis=2)                           # (R, T, nh, N)
    Cm = jnp.repeat(xbc[..., di + G * N:].reshape(R, T, G, N),
                    nh // G, axis=2)
    dt = jax.nn.softplus(dt + p["dt_bias"])                    # (R, T, nh)
    A = -jnp.exp(p["A_log"])                                   # (nh,)

    def step(s, inp):
        x_t, B_t, C_t, dt_t = inp
        s = jnp.exp(dt_t * A)[..., None, None] * s \
            + (dt_t[..., None] * x_t)[..., None] * B_t[:, :, None, :]
        y = jnp.einsum("rhpn,rhn->rhp", q(s), q(C_t), precision=HIGHEST)
        return s, y

    s0 = jnp.zeros((R, nh, P, N), f32)
    _, ys = jax.lax.scan(step, s0, tuple(jnp.swapaxes(t, 0, 1)
                                         for t in (x, Bm, Cm, dt)))
    y = jnp.swapaxes(ys, 0, 1) + p["D"][:, None] * x           # (R, T, nh, P)
    y = y.reshape(R, T, di) * jax.nn.silu(z)
    y = _rms(y, p["norm"], a["norm_eps"])
    return _mm(y, p["w_out"].astype(f32), q)


def _attention(a: dict, q, h, p):
    """Causal grouped-query attention with no positional encoding."""
    f32 = jnp.float32
    R, T, d = h.shape
    H, Hkv, dh = a["n_heads"], a["n_kv_heads"], a["head_dim"]
    qh = _mm(h, p["wq"].astype(f32).reshape(d, H * dh), q).reshape(
        R, T, H, dh)
    kh = _mm(h, p["wk"].astype(f32).reshape(d, Hkv * dh), q).reshape(
        R, T, Hkv, dh)
    vh = _mm(h, p["wv"].astype(f32).reshape(d, Hkv * dh), q).reshape(
        R, T, Hkv, dh)
    kh = jnp.repeat(kh, H // Hkv, axis=2)     # head j reads kv head j // g
    vh = jnp.repeat(vh, H // Hkv, axis=2)
    s = jnp.einsum("rqhk,rshk->rhqs", q(qh), q(kh), precision=HIGHEST) \
        * a["attn_scale"]
    causal = jnp.tril(jnp.ones((T, T), bool))
    pr = jax.nn.softmax(jnp.where(causal[None, None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("rhqs,rshk->rqhk", q(pr), q(vh), precision=HIGHEST)
    return _mm(o.reshape(R, T, H * dh),
               p["wo"].astype(f32).reshape(H * dh, d), q)


def _moe(a: dict, q, h, m):
    """The routed experts held here plus the shared expert, on h (N, d)."""
    f32 = jnp.float32
    first = a["experts_first"]
    held = a["experts_held"] or a["n_experts"]
    probs = jax.nn.softmax(_mm(h, m["router"].astype(f32), q), axis=-1)
    top_w, top_i = jax.lax.top_k(probs, a["moe_top_k"])
    top_w = top_w / jnp.sum(top_w, -1, keepdims=True)
    comb = jnp.zeros_like(probs).at[
        jnp.arange(h.shape[0])[:, None], top_i].set(top_w)    # (N, E)
    comb = comb[:, first:first + held]
    gt = jnp.einsum("td,edf->etf", q(h), q(m["w_gate"].astype(f32)),
                    precision=HIGHEST)
    up = jnp.einsum("td,edf->etf", q(h), q(m["w_up"].astype(f32)),
                    precision=HIGHEST)
    y = jnp.einsum("etf,efd->etd", q(jax.nn.silu(gt) * up),
                   q(m["w_down"].astype(f32)), precision=HIGHEST)
    y = jnp.einsum("etd,te->td", y, comb, precision=HIGHEST)
    s = m["shared"]
    return y + _swiglu(h, s["w_gate"], s["w_up"], s["w_down"], q)


def _layer(a: dict, kind: str, q, x, p):
    """One decoder layer on x (R, T, d) fp32."""
    R, T, d = x.shape
    eps, rm = a["norm_eps"], a["residual_multiplier"]
    h = _rms(x, p["ln_mix"], eps)
    mix = _attention if kind == "attn" else _mamba2
    x = x + rm * mix(a, q, h, p["mix"])
    h = _rms(x, p["ln_mlp"], eps).reshape(R * T, d)
    return x + rm * _moe(a, q, h, p["mlp"]).reshape(R, T, d)


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
    period = a["hybrid_period"]
    fns = {k: jax.jit(lambda x, p, k=k: _layer(a, k, q, x, p))
           for k in ("attn", "mamba")}
    x = jnp.take(params["embed"], jnp.asarray(seqs), axis=0).astype(f32) \
        * a["embedding_multiplier"]
    for i, kind in enumerate(_kinds(a)):
        sub = params["stack"][f"sub{i % period}"]
        x = fns[kind](x, jax.tree.map(lambda t: t[i // period], sub))
    x = _rms(x[:, first_pos:], params["final_norm"], a["norm_eps"])
    V = a["vocab_size"]
    head = params["embed"][:V].astype(f32)
    return jax.jit(lambda x, w: jnp.einsum(
        "rtd,vd->rtv", q(x), q(w), precision=HIGHEST)
        / a["logits_scaling"])(x, head)


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
    prompt's last position), each through every layer, and the tied LM
    head at the ``new_tokens`` positions whose logits pick a token.

    Per token, a Mamba-2 layer: in_proj, the depthwise conv, the state
    update (3 operations a state element: decay, input outer product, add)
    and read-out (2), out_proj; an attention layer: its projections, and
    causal attention over its own context; every layer: the router, the
    shared expert, and the routed experts held here, top_k · held /
    n_experts of them (the expected routes that land on this chip).
    Padding and experts computed needlessly do not count."""
    d, L = a["d_model"], a["n_layers"]
    H, Hkv, dh = a["n_heads"], a["n_kv_heads"], a["head_dim"]
    E, k, ff = a["n_experts"], a["moe_top_k"], a["d_ff_expert"]
    held = a["experts_held"] or E
    di, nh, P, G, N, K, cd = _ssm_dims(a)
    kinds = _kinds(a)
    n_attn = kinds.count("attn")
    mamba = (2 * d * (di + cd + nh) + 2 * K * cd + 5 * nh * P * N
             + 2 * di * d)
    attn = 2 * d * (H + 2 * Hkv) * dh + 2 * H * dh * d
    mlp = 2 * d * E + k * held / E * 3 * 2 * d * ff \
        + 3 * 2 * d * a["d_ff_shared"]
    T = prompt_len + new_tokens - 1
    ctx = T * (T + 1) // 2                      # Σ attended positions
    per_token = (L - n_attn) * mamba + n_attn * attn + L * mlp
    return float(T * per_token + n_attn * 4 * H * dh * ctx
                 + new_tokens * 2 * d * a["vocab_size"])
