"""Mesh construction.

The pod axis is the WAN-like (DCN) boundary GeoCoCo's communicator owns;
`data` x `model` is one pod's ICI torus.  Defined as functions (never
module-level constants) so importing this module touches no jax device
state.

Every axis is ``AxisType.Auto``: the device plane is GSPMD over `data` /
`model` with a manual `pod` region (``jax.shard_map(axis_names={"pod"})``).
``jax.make_mesh`` defaults to ``Explicit`` axes, under which
``with_sharding_constraint`` and ``jnp.repeat`` inside the pod region are
refused.
"""

from __future__ import annotations

import os

import jax
from jax.sharding import AxisType, Mesh

__all__ = [
    "make_mesh",
    "make_production_mesh",
    "make_small_mesh",
    "default_mesh",
    "default_mesh_shape",
    "split_cpu_host",
]

AXES = ("pod", "data", "model")


def split_cpu_host(n_devices: int = 8) -> None:
    """Under ``JAX_PLATFORMS=cpu``, present the host as ``n_devices``
    virtual devices; on any other platform do nothing.  Must run before
    the backend starts (the first device query)."""
    if os.environ.get("JAX_PLATFORMS") == "cpu":
        os.environ.setdefault(
            "XLA_FLAGS", f"--xla_force_host_platform_device_count={n_devices}"
        )


def make_mesh(axis_shapes, axis_names, *, devices=None) -> Mesh:
    """``jax.make_mesh`` with every axis ``Auto`` (GSPMD-partitioned)."""
    return jax.make_mesh(
        tuple(axis_shapes), tuple(axis_names),
        axis_types=(AxisType.Auto,) * len(axis_names), devices=devices,
    )


def default_mesh_shape(n_devices: int) -> tuple[int, int, int]:
    """(pod, data, model) for ``n_devices``: two pods when the count is
    even, then a model axis of 2 once each pod holds at least four
    devices.  1 -> (1, 1, 1), 4 -> (2, 2, 1), 8 -> (2, 2, 2)."""
    pod = 2 if n_devices > 1 and n_devices % 2 == 0 else 1
    per_pod = n_devices // pod
    model = 2 if per_pod >= 4 and per_pod % 2 == 0 else 1
    return pod, per_pod // model, model


def default_mesh() -> Mesh:
    """(pod, data, model) mesh over every device JAX sees."""
    return make_mesh(default_mesh_shape(jax.device_count()), AXES)


def make_production_mesh(*, multi_pod: bool = False, reduced: bool = False):
    """Production mesh (512 devices), or the ``reduced`` 16-device tier —
    the same axis layout scaled down so the dry-run compiles in CI."""
    if reduced:
        shape = (2, 2, 4) if multi_pod else (4, 4)
    else:
        shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_small_mesh(shape=(2, 2, 2), axes=AXES):
    """Reduced mesh for CPU integration tests (8 host devices)."""
    return make_mesh(shape, axes)
