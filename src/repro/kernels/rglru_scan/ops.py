"""Public jit'd wrapper for the RG-LRU scan kernel."""

from __future__ import annotations

import jax.numpy as jnp

from .ref import rglru_scan_ref
from .rglru_scan import rglru_scan_pallas

__all__ = ["rglru_scan", "rglru_scan_ref"]


def rglru_scan(
    a: jnp.ndarray,
    b: jnp.ndarray,
    h0: jnp.ndarray,
    *,
    use_kernel: bool = True,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    if not use_kernel:
        return rglru_scan_ref(a, b, h0)
    return rglru_scan_pallas(
        a.astype(jnp.float32), b.astype(jnp.float32), h0.astype(jnp.float32),
        interpret=interpret,
    )
