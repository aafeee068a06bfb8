"""Public jit'd wrapper for the WKV6 kernel: model-facing shapes, padding."""

from __future__ import annotations

import jax.numpy as jnp

from .ref import wkv6_ref
from .rwkv6_wkv import wkv6_pallas

__all__ = ["wkv6", "wkv6_ref"]


def wkv6(
    r: jnp.ndarray,    # (B, T, H, N)
    k: jnp.ndarray,
    v: jnp.ndarray,
    w: jnp.ndarray,
    u: jnp.ndarray,    # (H, N)
    state: jnp.ndarray,  # (B, H, N, N)
    *,
    use_kernel: bool = True,
    interpret: bool | None = None,
    time_chunk: int = 128,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Model-facing WKV6: returns (y (B,T,H,N), final_state)."""
    if not use_kernel:
        return wkv6_ref(r, k, v, w, u, state)
    b, t, h, n = r.shape

    def to_bh(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, t, n).astype(jnp.float32)

    u_bh = jnp.broadcast_to(u[None], (b, h, n)).reshape(b * h, n).astype(jnp.float32)
    s_bh = state.reshape(b * h, n, n).astype(jnp.float32)
    y, s_fin = wkv6_pallas(
        to_bh(r), to_bh(k), to_bh(v), to_bh(w), u_bh, s_bh,
        time_chunk=time_chunk, interpret=interpret,
    )
    y = y.reshape(b, h, t, n).transpose(0, 2, 1, 3)
    return y, s_fin.reshape(b, h, n, n)
