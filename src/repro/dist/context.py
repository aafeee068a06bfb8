"""Distribution context: mesh-aware sharding decisions inside model code.

``build_train_step``/``build_serve_step`` enter :func:`distribution` around
the model forward so layers (attention head pinning, MoE expert
parallelism) can consult the active mesh without threading it through every
call.  :func:`current` returns ``None`` outside any distributed region, in
which case layers fall back to their single-device paths.
"""

from __future__ import annotations

import contextlib
import dataclasses

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["DistContext", "distribution", "current"]


@dataclasses.dataclass(frozen=True)
class DistContext:
    mesh: Mesh

    @property
    def data_size(self) -> int:
        return self.mesh.shape.get("data", 1)

    @property
    def model_size(self) -> int:
        return self.mesh.shape.get("model", 1)

    def constrain_heads(self, x: jax.Array) -> jax.Array:
        """Pin the head axis of a (B, S, H, D) tensor to ``model`` when it
        divides — and never let the partitioner split ``head_dim`` (it
        otherwise factors the contraction dim and emits an all-reduce per
        attention chunk pair)."""
        dm = self.model_size
        if dm <= 1 or getattr(x, "ndim", 0) != 4 or x.shape[2] % dm:
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, P(None, None, "model", None))
        )

    def shard_map(self, fn, *, in_specs, out_specs, axis_names):
        """Manual subregion over ``axis_names`` of the context mesh; the
        other axes stay with GSPMD.  Inside an enclosing manual region (the
        train step's pod region) it nests on that region's mesh."""
        mesh = jax.sharding.get_abstract_mesh()
        return jax.shard_map(
            fn, mesh=self.mesh if mesh.empty else mesh, in_specs=in_specs,
            out_specs=out_specs, axis_names=set(axis_names), check_vma=False,
        )


_STACK: list[DistContext] = []


@contextlib.contextmanager
def distribution(mesh: Mesh):
    """Activate a distribution context for the enclosed model code."""
    _STACK.append(DistContext(mesh))
    try:
        yield _STACK[-1]
    finally:
        _STACK.pop()


def current() -> DistContext | None:
    return _STACK[-1] if _STACK else None
