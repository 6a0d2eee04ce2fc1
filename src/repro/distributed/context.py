"""Mesh/axis context threaded through model code.

``Dist`` names the mesh axes and carries the sizes model code needs for
static shape math (MoE capacities, padding). ``Dist.single()`` is the
1-device stand-in used by smoke tests and examples — model code never
branches on "is distributed", only on axis sizes.

Axis convention:
    pod    — outer data parallelism (slow inter-pod links); optional
    data   — intra-pod data parallelism / MoE expert ownership
    model  — tensor parallelism (heads, ffn, vocab) / MoE ffn sharding
"""

from __future__ import annotations

from dataclasses import dataclass

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclass(frozen=True)
class Dist:
    mesh: Mesh | None = None
    pod_axis: str | None = None
    data_axis: str = "data"
    model_axis: str = "model"

    @classmethod
    def single(cls) -> "Dist":
        return cls(mesh=None)

    @classmethod
    def from_mesh(cls, mesh: Mesh) -> "Dist":
        names = mesh.axis_names
        return cls(mesh=mesh,
                   pod_axis="pod" if "pod" in names else None,
                   data_axis="data", model_axis="model")

    # -- sizes -----------------------------------------------------------
    def axis_size(self, name: str | None) -> int:
        if self.mesh is None or name is None:
            return 1
        return self.mesh.shape[name]

    @property
    def n_pod(self) -> int:
        return self.axis_size(self.pod_axis)

    @property
    def n_data(self) -> int:
        return self.axis_size(self.data_axis) if self.mesh is not None else 1

    @property
    def n_model(self) -> int:
        return self.axis_size(self.model_axis) if self.mesh is not None else 1

    @property
    def n_devices(self) -> int:
        return self.n_pod * self.n_data * self.n_model

    # -- batch/token axes --------------------------------------------------
    @property
    def batch_axes(self):
        """Mesh axes that shard the batch/token dimension."""
        if self.mesh is None:
            return None
        return ((self.pod_axis, self.data_axis) if self.pod_axis
                else (self.data_axis,))

    def spec(self, *axes) -> P:
        """PartitionSpec helper; None entries pass through."""
        return P(*axes)

    def sharding(self, *axes) -> NamedSharding | None:
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, P(*axes))
