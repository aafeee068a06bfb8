"""Public jit'd wrapper for the versioned CRDT merge kernel."""

from __future__ import annotations

import jax.numpy as jnp

from .crdt_merge import crdt_merge_pallas
from .ref import crdt_merge_ref

__all__ = ["crdt_merge", "crdt_merge_many", "crdt_merge_ref"]


def crdt_merge(
    val_a, ver_a, val_b, ver_b, *, use_kernel: bool = True,
    interpret: bool | None = None,
):
    """Merge two versioned slot batches: (M, N) payloads + (M,) versions.

    ``interpret=None`` interprets on the CPU backend only (see
    :func:`repro.kernels.interpret_mode`)."""
    if not use_kernel:
        return crdt_merge_ref(val_a, ver_a, val_b, ver_b)
    return crdt_merge_pallas(
        val_a, ver_a.astype(jnp.int32), val_b, ver_b.astype(jnp.int32),
        interpret=interpret,
    )


def crdt_merge_many(batches, *, use_kernel: bool = True):
    """Fold-merge a list of (values, versions) batches (ACI => any order)."""
    val, ver = batches[0]
    ver = ver.astype(jnp.int32)
    for vb, rb in batches[1:]:
        val, ver = crdt_merge(val, ver, vb, rb, use_kernel=use_kernel)
    return val, ver
