"""Model facade: init / train loss / prefill / decode for every family.

Families share the grouped-scan stack (``transformer.py``); this module owns
embeddings (token / patch-prefix / audio-frontend-stub), the LM head with
sequence-chunked cross-entropy (full (B,S,V) logits never materialize),
whisper's encoder + per-layer cross-K/V, and the cache plumbing.

Vocab is physically padded to a multiple of 2048 so the head shards over
any ``model`` axis (whisper's 51866, granite-moe's 49155); padded rows are
masked to −1e30 before softmax.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.distributed.context import Dist
from repro.models import transformer as tf
from repro.models.config import ArchConfig
from repro.models.layers import dtype_of, rms_norm

VOCAB_PAD_UNIT = 2048


def padded_vocab(v: int) -> int:
    return ((v + VOCAB_PAD_UNIT - 1) // VOCAB_PAD_UNIT) * VOCAB_PAD_UNIT


def _sinusoid_at(positions: jax.Array, dim: int) -> jax.Array:
    """Absolute sinusoidal embeddings at given positions (whisper)."""
    half = dim // 2
    i = jnp.arange(half, dtype=jnp.float32)
    inv = jnp.power(10000.0, -i / half)
    ang = positions[..., None].astype(jnp.float32) * inv
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


@dataclass
class Model:
    cfg: ArchConfig
    dist: Dist | None = None

    # ------------------------------------------------------------- params
    def init_params(self, key) -> dict:
        cfg = self.cfg
        dt = dtype_of(cfg)
        vp = padded_vocab(cfg.vocab_size)
        keys = jax.random.split(key, 6)
        params = {
            "embed": (jax.random.normal(keys[0], (vp, cfg.d_model), jnp.float32)
                      * cfg.d_model ** -0.5).astype(dt),
            "head": (jax.random.normal(keys[1], (vp, cfg.d_model), jnp.float32)
                     * cfg.d_model ** -0.5).astype(dt),
            "final_norm": jnp.zeros((cfg.d_model,), jnp.float32),
            "stack": tf.init_stack(keys[2], cfg),
        }
        if cfg.tie_embeddings:
            del params["head"]
        if cfg.family == "encdec":
            enc_cfg = self._enc_cfg()
            params["enc"] = {
                "proj": (jax.random.normal(
                    keys[3], (cfg.enc_dim, cfg.d_model), jnp.float32)
                    * cfg.enc_dim ** -0.5).astype(dt),
                "stack": tf.init_stack(keys[4], enc_cfg),
                "final_norm": jnp.zeros((cfg.d_model,), jnp.float32),
            }
        return params

    def param_shapes(self) -> dict:
        return jax.eval_shape(lambda: self.init_params(jax.random.key(0)))

    def _enc_cfg(self) -> ArchConfig:
        """Encoder stack config: non-causal dense attention layers."""
        from dataclasses import replace
        return replace(self.cfg, family="dense", n_layers=self.cfg.enc_layers,
                       n_experts=0, moe_top_k=0, sliding_window=None,
                       local_global_alternating=False)

    # ------------------------------------------------------------ embedding
    def _embed_tokens(self, params, tokens):
        x = jnp.take(params["embed"], tokens, axis=0)
        if self.cfg.final_softcap is not None:   # gemma-style embed scaling
            x = x * jnp.asarray(self.cfg.d_model ** 0.5, x.dtype)
        if self.cfg.embedding_multiplier != 1.0:
            x = x * jnp.asarray(self.cfg.embedding_multiplier, x.dtype)
        return x

    def _head(self, params):
        """The LM head's (Vp, d) table: the embedding's where tied."""
        return params["embed"] if self.cfg.tie_embeddings else params["head"]

    def _scale_logits(self, logits):
        cfg = self.cfg
        if cfg.logits_scaling != 1.0:
            logits = logits / cfg.logits_scaling
        if cfg.final_softcap is not None:
            logits = cfg.final_softcap * jnp.tanh(logits / cfg.final_softcap)
        return logits

    # ------------------------------------------------------------- encoder
    def _encode(self, params, audio):
        """Whisper encoder on precomputed frame embeddings (frontend stub)."""
        cfg = self.cfg
        x = jnp.einsum("bcd,de->bce", audio.astype(dtype_of(cfg)),
                       params["enc"]["proj"].astype(dtype_of(cfg)))
        pos = jnp.arange(x.shape[1])
        x = x + _sinusoid_at(pos, cfg.d_model).astype(x.dtype)
        enc_cfg = self._enc_cfg()
        group, _ = tf.layer_groups(enc_cfg)
        group = [tf.SubLayerSpec(kind="attn", mlp="dense", window=None,
                                 causal=False)] * len(group)
        x, _, _ = tf.stack_apply(x, params["enc"]["stack"], enc_cfg,
                                 self.dist, mode="train",
                                 positions=pos, group=group)
        return rms_norm(x, params["enc"]["final_norm"], cfg.norm_eps)

    def _cross_kv(self, params, enc_out):
        """Per-decoder-layer cross K/V, group-stacked for the scan."""
        wk = params["stack"]["sub0"]["cross"]["wk"]      # (G, d, Hkv, dh)
        wv = params["stack"]["sub0"]["cross"]["wv"]
        k = jnp.einsum("bcd,gdhk->gbchk", enc_out, wk.astype(enc_out.dtype))
        v = jnp.einsum("bcd,gdhk->gbchk", enc_out, wv.astype(enc_out.dtype))
        return {"k": k, "v": v}

    def _dec_inputs(self, params, tokens, positions):
        x = self._embed_tokens(params, tokens)
        if self.cfg.family == "encdec":
            x = x + _sinusoid_at(positions, self.cfg.d_model).astype(x.dtype)
        return x

    # ------------------------------------------------------------- training
    def loss_fn(self, params, batch) -> tuple[jax.Array, dict]:
        """batch: tokens (B,S), labels (B,S) int32 (−1 = masked), plus
        family extras: patches (B,P,d) [vlm], audio (B,ctx,enc_dim) [encdec].
        """
        cfg = self.cfg
        tokens, labels = batch["tokens"], batch["labels"]
        positions = jnp.arange(tokens.shape[1])
        enc_kv = None
        if cfg.family == "encdec":
            enc_out = self._encode(params, batch["audio"])
            enc_kv = self._cross_kv(params, enc_out)
        x = self._dec_inputs(params, tokens, positions)
        if cfg.family == "vlm":
            patches = batch["patches"].astype(x.dtype)
            x = jnp.concatenate([patches, x], axis=1)
            positions = jnp.arange(x.shape[1])
            pad = jnp.full(patches.shape[:2], -1, labels.dtype)
            labels = jnp.concatenate([pad, labels], axis=1)

        x, _, aux = tf.stack_apply(x, params["stack"], cfg, self.dist,
                                   mode="train", positions=positions,
                                   enc_kv=enc_kv)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        loss, n_tok = self._chunked_xent(params, x, labels)
        total = loss + cfg.router_aux_weight * aux
        return total, {"xent": loss, "aux": aux, "tokens": n_tok}

    def _chunked_xent(self, params, x, labels):
        """Sequence-chunked cross-entropy; (B,S,V) never materializes."""
        cfg = self.cfg
        B, S, d = x.shape
        chunk = min(cfg.loss_chunk, S)
        if S % chunk:
            chunk = S
        nc = S // chunk
        head = self._head(params)
        vp = head.shape[0]
        vmask = (jnp.arange(vp) < cfg.vocab_size)

        def body(carry, inp):
            xc, lc = inp                                  # (B,c,d), (B,c)
            logits = self._scale_logits(jnp.einsum(
                "bcd,vd->bcv", xc.astype(jnp.float32),
                head.astype(jnp.float32)))
            logits = jnp.where(vmask[None, None], logits, -1e30)
            logp = jax.nn.log_softmax(logits, axis=-1)
            take = jnp.take_along_axis(
                logp, jnp.maximum(lc, 0)[..., None], axis=-1)[..., 0]
            mask = (lc >= 0).astype(jnp.float32)
            return (carry[0] + jnp.sum(-take * mask),
                    carry[1] + jnp.sum(mask)), None

        xs = (x.reshape(B, nc, chunk, d).swapaxes(0, 1),
              labels.reshape(B, nc, chunk).swapaxes(0, 1))
        (nll, cnt), _ = jax.lax.scan(body, (jnp.zeros(()), jnp.zeros(())), xs)
        return nll / jnp.maximum(cnt, 1.0), cnt

    # ------------------------------------------------------------- serving
    def init_cache(self, batch: int, max_len: int) -> dict:
        return tf.init_cache(self.cfg, batch, max_len)

    def _logits_last(self, params, x_last):
        cfg = self.cfg
        head = self._head(params)
        logits = self._scale_logits(jnp.einsum(
            "bd,vd->bv", x_last.astype(jnp.float32),
            head.astype(jnp.float32)))
        vp = head.shape[0]
        return jnp.where(jnp.arange(vp)[None, :] < cfg.vocab_size,
                         logits, -1e30)

    def prefill(self, params, batch, max_len: int):
        """Returns (last-token logits (B, Vp), cache dict, kv_len (B,)).

        ``cache`` = {"stack": ..., "enc_kv": ...?}; chunked at
        ``cfg.prefill_chunk`` (static offsets, unrolled).
        """
        cfg = self.cfg
        tokens = batch["tokens"]
        B, S = tokens.shape
        enc_kv = None
        if cfg.family == "encdec":
            enc_out = self._encode(params, batch["audio"])
            enc_kv = self._cross_kv(params, enc_out)

        positions = jnp.arange(S)
        x = self._dec_inputs(params, tokens, positions)
        if cfg.family == "vlm":
            patches = batch["patches"].astype(x.dtype)
            x = jnp.concatenate([patches, x], axis=1)
            S = x.shape[1]
            positions = jnp.arange(S)

        # VLM prefix tokens count toward context: grow the cache if needed.
        cache = tf.init_cache(cfg, B, max(max_len, S))
        chunk = cfg.prefill_chunk or S
        if S % chunk:
            chunk = S
        aux_total = jnp.zeros(())
        for off in range(0, S, chunk):
            xc = jax.lax.slice_in_dim(x, off, off + chunk, axis=1)
            pos = positions[off:off + chunk]
            xc, cache, aux = tf.stack_apply(
                xc, params["stack"], cfg, self.dist, mode="prefill",
                positions=pos, cache=cache, kv_len=None, kv_offset=off,
                enc_kv=enc_kv)
            aux_total = aux_total + aux
        x_last = rms_norm(xc[:, -1], params["final_norm"], cfg.norm_eps)
        logits = self._logits_last(params, x_last)
        out_cache = {"stack": cache}
        if enc_kv is not None:
            out_cache["enc_kv"] = enc_kv
        return logits, out_cache, jnp.full((B,), S, jnp.int32)

    def decode_step(self, params, cache: dict, tokens: jax.Array,
                    kv_len: jax.Array):
        """One token for every sequence. tokens (B,), kv_len (B,).
        Returns (logits (B, Vp), new_cache, kv_len + 1)."""
        cfg = self.cfg
        B = tokens.shape[0]
        x = self._dec_inputs(params, tokens[:, None], kv_len[:, None])
        x, new_stack, _ = tf.stack_apply(
            x, params["stack"], cfg, self.dist, mode="decode",
            positions=kv_len[:, None], cache=cache["stack"], kv_len=kv_len,
            enc_kv=cache.get("enc_kv"))
        x_last = rms_norm(x[:, 0], params["final_norm"], cfg.norm_eps)
        logits = self._logits_last(params, x_last)
        new_cache = dict(cache)
        new_cache["stack"] = new_stack
        return logits, new_cache, kv_len + 1
