"""Logical-axis sharding rules (MaxText-style), resolved per (arch, mesh).

Rules walk the parameter pytree by path and emit ``PartitionSpec``s:

  * feature axes (heads, ffn, vocab, d_inner) → ``model``  (TP)
  * training adds FSDP: d_model dims → ``data``; the MoE expert axis is
    *stored* over the widest dividing prefix of (pod, data) — kimi-k2's
    1 T params shard across pods at rest and are all-gathered per layer
    into the data-owned compute layout inside the scan (GSPMD inserts the
    gather from the shard_map in_spec mismatch)
  * serving replicates weights over ``data``; batch/KV shard over
    (pod, data), the KV **sequence** goes to ``model`` when kv_heads don't
    divide the model axis, and to (data, model) for batch-1 long context
  * group-stacked leaves (under "stack") get a leading ``None``

Divisibility is checked against the actual mesh: anything non-divisible
falls back to replication on that axis (recorded in ``notes``) — 8-head
gemma2 attention ends up TP-replicated while its 9216-wide FFN TP-shards.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.distributed.context import Dist
from repro.models.config import ArchConfig


def _axis_size(dist: Dist, axis) -> int:
    if axis is None:
        return 1
    size = 1
    for a in (axis if isinstance(axis, tuple) else (axis,)):
        size *= dist.axis_size(a)
    return size


def _fit(dist: Dist, dim: int, *candidates):
    """First candidate axis (or axis tuple) whose size divides dim."""
    for axis in candidates:
        if axis is None:
            return None
        if dim % _axis_size(dist, axis) == 0:
            return axis
    return None


@dataclass
class ShardingPlan:
    params: dict                      # pytree of PartitionSpec
    notes: list[str] = field(default_factory=list)

    def shardings(self, mesh) -> dict:
        return jax.tree.map(
            lambda s: NamedSharding(mesh, s), self.params,
            is_leaf=lambda x: isinstance(x, P))


def param_plan(cfg: ArchConfig, dist: Dist, *, training: bool) -> ShardingPlan:
    """PartitionSpec pytree matching ``Model.init_params`` structure."""
    shapes = _shape_tree(cfg)
    notes: list[str] = []
    fsdp = dist.data_axis if training else None
    model = dist.model_axis
    # Expert storage: widest dividing prefix of (pod, data); replicate else.
    expert_candidates = []
    if training and dist.pod_axis:
        expert_candidates.append((dist.pod_axis, dist.data_axis))
    expert_candidates.extend([(dist.data_axis,), None])

    def rule(path: str, shape: tuple[int, ...]) -> P:
        stacked = "stack." in path
        base = shape[1:] if stacked else shape
        leaf = path.split(".")[-1]

        def wrap(*axes):
            assert len(axes) == len(base), (path, axes, base)
            spec = tuple(_fit(dist, base[i], a, None)
                         for i, a in enumerate(axes))
            for i, (want, got) in enumerate(zip(axes, spec)):
                if want is not None and got is None:
                    notes.append(f"{path}: dim{i}={base[i]} not divisible by "
                                 f"{want}; replicated")
            return P(*(((None,) + spec) if stacked else spec))

        if leaf in ("embed", "head"):
            return wrap(model, fsdp)
        if leaf in ("final_norm", "ln_mix", "ln_mlp", "ln_cross", "conv_b",
                    "dt_bias", "D"):
            return wrap(*([None] * len(base)))
        if leaf in ("wq", "wk", "wv"):
            return wrap(fsdp, model, None)
        if leaf == "wo":
            return wrap(model, None, fsdp)
        if len(base) == 3 and leaf in ("w_gate", "w_up"):     # MoE experts
            e_axis = _fit(dist, base[0], *expert_candidates)
            return wrap(e_axis, None, model)
        if len(base) == 3 and leaf == "w_down":
            e_axis = _fit(dist, base[0], *expert_candidates)
            return wrap(e_axis, model, None)
        if leaf in ("w_gate", "w_up"):                         # dense MLP
            return wrap(fsdp, model)
        if leaf == "w_down":
            return wrap(model, fsdp)
        if leaf == "router":
            return wrap(fsdp, None)
        if leaf == "w_in":                                     # mamba (d, 2di)
            return wrap(fsdp, model)
        if leaf == "conv_w":
            return wrap(None, model)
        if leaf == "w_x_proj":
            return wrap(model, None)
        if leaf == "w_dt":
            return wrap(None, model)
        if leaf == "A_log":                    # mamba (di, N); mamba2 (H,)
            return wrap(model, None) if len(base) == 2 else wrap(None)
        if leaf == "w_out":                                    # (di, d)
            return wrap(model, fsdp)
        if leaf == "proj":                                     # whisper frontend
            return wrap(None, fsdp)
        notes.append(f"replicated (no rule): {path} {shape}")
        return P(*([None] * len(shape)))

    specs = _map_with_path(shapes, rule)
    return ShardingPlan(params=specs, notes=notes)


def _shape_tree(cfg: ArchConfig) -> dict:
    from repro.models.model import Model
    m = Model(cfg)
    return jax.tree.map(lambda s: s.shape, m.param_shapes())


def _map_with_path(tree, fn, prefix=""):
    if isinstance(tree, dict):
        return {k: _map_with_path(v, fn, prefix + k + ".")
                for k, v in tree.items()}
    return fn(prefix.rstrip("."), tree)


# ---------------------------------------------------------------------------
# Input / cache specs per shape kind.
# ---------------------------------------------------------------------------

def batch_spec(dist: Dist, batch: int):
    """Shard batch over (pod, data) if divisible; fall back to data; none."""
    cands = []
    if dist.pod_axis:
        cands.append((dist.pod_axis, dist.data_axis))
    cands.extend([(dist.data_axis,), None])
    return _fit(dist, batch, *cands)


def input_specs_train(cfg: ArchConfig, dist: Dist, batch: int) -> dict:
    b = batch_spec(dist, batch)
    specs = {"tokens": P(b, None), "labels": P(b, None)}
    if cfg.family == "vlm":
        specs["patches"] = P(b, None, None)
    if cfg.family == "encdec":
        specs["audio"] = P(b, None, None)
    return specs


def cache_specs(cfg: ArchConfig, dist: Dist, batch: int, seq_len: int) -> dict:
    """Group-stacked cache PartitionSpecs (mirrors tf.init_cache)."""
    from repro.models.transformer import layer_groups
    group, _ = layer_groups(cfg)
    b = batch_spec(dist, batch)
    if b is not None:
        if cfg.n_kv_heads % max(1, dist.n_model) == 0:
            head_axis, seq_axis = dist.model_axis, None
        else:
            head_axis, seq_axis = None, dist.model_axis
    else:
        # batch-1 long context: shard the sequence over everything.
        head_axis = None
        seq_axis = _fit(dist, seq_len,
                        (dist.data_axis, dist.model_axis), None)
    di_axis = _fit(dist, cfg.ssm_d_inner, dist.model_axis, None)

    out = {}
    for i, spec in enumerate(group):
        if spec.kind == "attn":
            kv = P(None, b, seq_axis, head_axis, None)
            out[f"sub{i}"] = {"k": kv, "v": kv}
        else:
            out[f"sub{i}"] = {
                "h": P(None, b, di_axis, None),
                "conv": P(None, b, None, di_axis),
            }
    return out


def enc_kv_spec(cfg: ArchConfig, dist: Dist, batch: int) -> dict:
    b = batch_spec(dist, batch)
    s = P(None, b, None, None, None)
    return {"k": s, "v": s}


def opt_plan(param_specs: dict, opt_shapes: dict, dist: Dist) -> dict:
    """Moment specs mirror param specs; int8 block scales drop the last-axis
    sharding unless the block count still divides it."""

    def moment_spec(pspec: P, mo_shape) -> dict:
        if mo_shape["s"] is None:
            return {"q": pspec, "s": None}
        s_shape = mo_shape["s"].shape
        last = pspec[-1] if len(pspec) else None
        log_domain = len(s_shape) == mo_shape["q"].ndim + 1
        blocks = s_shape[-2] if log_domain else s_shape[-1]
        s_spec = (*pspec[:-1], _fit(dist, blocks, last, None))
        if log_domain:
            s_spec = (*s_spec, None)
        return {"q": pspec, "s": P(*s_spec)}

    def walk(spec_tree, shape_tree):
        if isinstance(spec_tree, P):
            return moment_spec(spec_tree, shape_tree)
        return {k: walk(spec_tree[k], shape_tree[k]) for k in spec_tree}

    return {
        "step": P(),
        "m": walk(param_specs, opt_shapes["m"]),
        "v": walk(param_specs, opt_shapes["v"]),
    }
