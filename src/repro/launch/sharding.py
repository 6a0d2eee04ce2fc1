"""Logical-axis sharding rules (MaxText-style), resolved per (arch, mesh).

Rules walk the parameter pytree by path and emit the serving plan's
``PartitionSpec``s:

  * feature axes (heads, ffn, vocab, d_inner) → ``model``  (TP)
  * d_model dims are replicated over ``data``; the MoE expert axis is
    stored over ``data`` when it divides (expert parallelism)
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


def param_plan(cfg: ArchConfig, dist: Dist) -> ShardingPlan:
    """PartitionSpec pytree matching ``Model.init_params`` structure."""
    shapes = _shape_tree(cfg)
    notes: list[str] = []
    model = dist.model_axis
    experts = (dist.data_axis,)

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
            return wrap(model, None)
        if leaf in ("final_norm", "ln_mix", "ln_mlp", "ln_cross", "conv_b",
                    "dt_bias", "D", "norm", "router", "proj"):
            return wrap(*([None] * len(base)))
        if leaf in ("wq", "wk", "wv"):
            return wrap(None, model, None)
        if leaf == "wo":
            return wrap(model, None, None)
        if len(base) == 3 and leaf in ("w_gate", "w_up"):     # MoE experts
            return wrap(experts, None, model)
        if len(base) == 3 and leaf == "w_down":
            return wrap(experts, model, None)
        if leaf in ("w_gate", "w_up"):                         # dense MLP
            return wrap(None, model)
        if leaf == "w_down":
            return wrap(model, None)
        if leaf == "w_in":                                     # mamba (d, 2di)
            return wrap(None, model)
        if leaf == "conv_w":
            return wrap(None, model)
        if leaf == "w_x_proj":
            return wrap(model, None)
        if leaf == "w_dt":
            return wrap(None, model)
        if leaf == "A_log":                    # mamba (di, N); mamba2 (H,)
            return wrap(model, None) if len(base) == 2 else wrap(None)
        if leaf == "w_out":                                    # (di, d)
            return wrap(model, None)
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


