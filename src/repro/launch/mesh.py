"""Production meshes.

``make_production_mesh`` is a FUNCTION so importing this module never
touches jax device state. Single pod: 16×16 = 256 chips (data, model).
Multi-pod: 2×16×16 = 512 chips (pod, data, model) — the pod axis is outer
data parallelism.

All mesh construction routes through ``make_mesh``, which requests Auto
axes (``jax.sharding.AxisType.Auto``): sharding is propagated by GSPMD
unless a ``shard_map`` or constraint says otherwise.
"""

from __future__ import annotations

import jax


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Mesh constructor with Auto axes — the ONLY way this repo builds
    meshes (tests/examples included, e.g. a (2,2,2) mini multi-pod)."""
    return jax.make_mesh(shape, axes, axis_types=(
        jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
