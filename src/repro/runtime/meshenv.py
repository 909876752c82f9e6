"""Mesh environment: one object threading distribution context through model code.

``MeshEnv`` wraps a ``jax.sharding.Mesh`` (or None for single-device CPU
runs) and knows which mesh axes mean "batch" (data parallel — ``data``,
plus ``pod`` on the multi-pod mesh) and which axis is tensor/expert
parallel (``model``).  Model code only ever asks the env for
``PartitionSpec``s and for ``constrain`` — it never hard-codes axis names,
so the same model runs on the 16×16 pod mesh, the 2×16×16 multi-pod mesh,
a tiny test mesh, or a single CPU device.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AxisName = Union[str, Tuple[str, ...], None]

def shard_map(f, *, mesh, in_specs, out_specs, check: bool = False):
    """``jax.shard_map`` with replication checking off by default — every
    call site in this repo passes explicit out_specs."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)


@dataclasses.dataclass(frozen=True)
class MeshEnv:
    mesh: Optional[Mesh] = None
    batch_axes: Tuple[str, ...] = ()
    model_axis: Optional[str] = None
    # §Perf: shard attention over the SEQUENCE instead of heads.  For
    # kv_dim ≪ d_model the collective per attention layer becomes an
    # all-gather of k/v instead of the residual stream (8× fewer bytes on
    # recurrentgemma's MQA); attention weights replicate over 'model'.
    context_parallel_attn: bool = False

    # ------------------------------------------------------------------
    @property
    def is_spmd(self) -> bool:
        return self.mesh is not None

    @property
    def tp(self) -> int:
        """Size of the tensor/expert-parallel axis."""
        if not self.is_spmd or self.model_axis is None:
            return 1
        return self.mesh.shape[self.model_axis]

    @property
    def dp(self) -> int:
        if not self.is_spmd:
            return 1
        n = 1
        for a in self.batch_axes:
            n *= self.mesh.shape[a]
        return n

    # ------------------------------------------------------------------
    def batch(self) -> AxisName:
        """Axis-name entry for a batch-sharded dim."""
        if not self.batch_axes:
            return None
        return self.batch_axes if len(self.batch_axes) > 1 else self.batch_axes[0]

    def batch_if(self, n: int) -> AxisName:
        """Batch axis entry only when dim ``n`` divides the DP size
        (shard_map needs exact divisibility; long_500k has batch 1)."""
        if self.dp > 1 and n % self.dp == 0:
            return self.batch()
        return None

    def model(self) -> AxisName:
        return self.model_axis

    def spec(self, *entries: AxisName) -> P:
        """Build a PartitionSpec, dropping axes when not SPMD."""
        if not self.is_spmd:
            return P()
        return P(*entries)

    def sharding(self, *entries: AxisName) -> Optional[NamedSharding]:
        if not self.is_spmd:
            return None
        return NamedSharding(self.mesh, self.spec(*entries))

    def constrain(self, x, *entries: AxisName):
        """with_sharding_constraint when SPMD, identity otherwise."""
        if not self.is_spmd:
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, P(*entries)))

    # ------------------------------------------------------------------
    def divides_model(self, n: int) -> bool:
        """True if dim ``n`` divides evenly over the model axis."""
        return self.tp <= 1 or (n % self.tp == 0)


CPU_ENV = MeshEnv()


def make_env(mesh: Optional[Mesh], *,
             context_parallel_attn: bool = False) -> MeshEnv:
    if mesh is None:
        return CPU_ENV
    names = tuple(mesh.axis_names)
    batch = tuple(a for a in names if a in ("pod", "data", "replica"))
    model = "model" if "model" in names else None
    return MeshEnv(mesh=mesh, batch_axes=batch, model_axis=model,
                   context_parallel_attn=context_parallel_attn)
