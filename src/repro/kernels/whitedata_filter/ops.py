"""Public jit'd wrapper for the white-data gradient filter.

Handles arbitrary pytrees / shapes by flattening to padded 2-D tiles, calls
the Pallas kernel (interpreted on the CPU backend, compiled on a TPU), and
exposes the pytree-level ``filter_gradient``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import round_up, sublane_tile
from .ref import whitedata_filter_ref
from .whitedata_filter import DEFAULT_BLOCK, whitedata_filter_pallas

__all__ = ["whitedata_filter", "filter_gradient", "whitedata_filter_ref"]


def whitedata_filter(
    g: jnp.ndarray,
    r: jnp.ndarray,
    tau,
    *,
    use_kernel: bool = True,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Filter one array (any shape).  Returns (send, new_r, kept).

    ``interpret=None`` interprets on the CPU backend only (see
    :func:`repro.kernels.interpret_mode`)."""
    if not use_kernel:
        return whitedata_filter_ref(g, r, tau)
    shape = g.shape
    size = g.size
    bm, bn = DEFAULT_BLOCK
    rows = -(-size // bn)
    bm = min(bm, round_up(rows, sublane_tile(g.dtype)))
    rows = round_up(rows, bm)
    pad = rows * bn - size
    gf, rf = g.reshape(-1), r.reshape(-1)
    if pad:
        gf = jnp.concatenate([gf, jnp.zeros(pad, g.dtype)])
        rf = jnp.concatenate([rf, jnp.zeros(pad, r.dtype)])
    send, new_r, kept = whitedata_filter_pallas(
        gf.reshape(rows, bn), rf.reshape(rows, bn), tau,
        block=(bm, bn), interpret=interpret,
    )
    # the zero padding passes |0| >= tau exactly when tau <= 0
    kept = kept - jnp.where(jnp.asarray(tau, jnp.float32) <= 0, pad, 0)
    send = send.reshape(-1)[:size].reshape(shape)
    new_r = new_r.reshape(-1)[:size].reshape(shape)
    return send, new_r, kept.astype(jnp.int32)


def filter_gradient(grads, residuals, tau, *, use_kernel: bool = True):
    """Apply the filter across a gradient pytree.

    Returns (send_tree, new_residual_tree, stats) with
    stats = {"kept": int32, "total": int32, "density": f32}.
    """
    leaves, treedef = jax.tree.flatten(grads)
    r_leaves = treedef.flatten_up_to(residuals)
    sends, new_rs, kepts = [], [], []
    total = 0
    for g, r in zip(leaves, r_leaves):
        s, nr, k = whitedata_filter(g, r, tau, use_kernel=use_kernel)
        sends.append(s)
        new_rs.append(nr)
        kepts.append(k)
        total += g.size
    kept = sum(kepts)
    stats = {
        "kept": kept,
        "total": jnp.asarray(total, jnp.int32),
        "density": kept.astype(jnp.float32) / total,
    }
    return treedef.unflatten(sends), treedef.unflatten(new_rs), stats
