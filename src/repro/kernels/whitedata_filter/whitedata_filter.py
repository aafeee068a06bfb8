"""Pallas TPU kernel: fused error-feedback white-data filter.

One VMEM pass computes accumulate + threshold + split + block-count, where
the naive jnp version makes four HBM round-trips over (g, r).  The op is
purely elementwise + a block reduction — a VPU kernel (no MXU), bound by
HBM bandwidth; fusing the four ops quarters the bytes moved.

Grid: 2-D over (M / bm, N / bn) row-major; each program handles one
(bm, bn) VMEM tile.  ``kept`` is a per-program (8, bn) block of per-lane
partial counts (a legal (8, 128)-tiled layout), summed by the wrapper — the
kernel stays free of cross-program communication.  ``tau`` is a scalar in
SMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import interpret_mode

DEFAULT_BLOCK = (256, 256)  # multiples of the (8, 128) float32 VMEM tile


def _filter_kernel(g_ref, r_ref, tau_ref, send_ref, newr_ref, kept_ref):
    g = g_ref[...].astype(jnp.float32)
    r = r_ref[...].astype(jnp.float32)
    tau = tau_ref[0]
    acc = g + r
    keep = jnp.abs(acc) >= tau
    send_ref[...] = jnp.where(keep, acc, 0.0).astype(send_ref.dtype)
    newr_ref[...] = jnp.where(keep, 0.0, acc).astype(newr_ref.dtype)
    bm, bn = keep.shape
    kept_ref[...] = keep.astype(jnp.int32).reshape(bm // 8, 8, bn).sum(axis=0)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def whitedata_filter_pallas(
    g: jnp.ndarray,
    r: jnp.ndarray,
    tau: jnp.ndarray,
    *,
    block: tuple[int, int] = DEFAULT_BLOCK,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """g, r: (M, N); tau: () scalar.  Returns (send, new_r, kept_count).

    M and N must be multiples of the block, whose rows are a multiple of 8
    and lanes of 128 (the wrapper in ``ops`` pads)."""
    interpret = interpret_mode(interpret)
    m, n = g.shape
    bm = min(block[0], m)
    bn = min(block[1], n)
    if m % bm or n % bn or bm % 8 or bn % 128:
        raise ValueError(f"shape {(m, n)} not tiled by block {(bm, bn)}")
    grid = (m // bm, n // bn)
    tau_arr = jnp.asarray(tau, jnp.float32).reshape(1)

    send, new_r, kept = pl.pallas_call(
        _filter_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((8, bn), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, n), g.dtype),
            jax.ShapeDtypeStruct((m, n), r.dtype),
            jax.ShapeDtypeStruct((grid[0] * 8, n), jnp.int32),
        ],
        interpret=interpret,
    )(g, r, tau_arr)
    return send, new_r, kept.sum(dtype=jnp.int32)
