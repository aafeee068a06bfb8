"""Pallas TPU kernel: row-versioned LWW merge of update batches.

TPU adaptation notes (vs a GPU implementation): a GPU merge typically uses
per-row CAS/atomic loops; on TPU the merge is a pure lattice join — a
predicated select on (version, payload) rows with no atomics, executed on
the VPU over (bm, bn) VMEM tiles.  Versions ride along as a (bm, 1) column
so one row-predicate broadcasts across the payload tile.

Grid: (M / bm, N / bn); versions are written only by the first column
program (j == 0) to avoid redundant stores.  Blocks are multiples of the
(sublane, 128) tile of the payload dtype; a payload that is not a whole
number of blocks is zero-padded in rows and lanes and sliced back.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .. import interpret_mode, round_up, sublane_tile

DEFAULT_BLOCK = (256, 256)


def _merge_kernel(va_ref, ra_ref, vb_ref, rb_ref, out_ref, over_ref):
    ver_a = ra_ref[...]                      # (bm, 1) int32
    ver_b = rb_ref[...]
    take_a = ver_a >= ver_b                  # (bm, 1) bool
    out_ref[...] = jnp.where(take_a, va_ref[...], vb_ref[...])
    @pl.when(pl.program_id(1) == 0)
    def _():
        over_ref[...] = jnp.maximum(ver_a, ver_b)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def crdt_merge_pallas(
    val_a: jnp.ndarray,
    ver_a: jnp.ndarray,
    val_b: jnp.ndarray,
    ver_b: jnp.ndarray,
    *,
    block: tuple[int, int] = DEFAULT_BLOCK,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    interpret = interpret_mode(interpret)
    m, n = val_a.shape
    bm = min(block[0], round_up(m, sublane_tile(val_a.dtype)))
    bn = min(block[1], round_up(n, 128))
    mp, np_ = round_up(m, bm), round_up(n, bn)
    if (mp, np_) != (m, n):
        pad = ((0, mp - m), (0, np_ - n))
        val_a, val_b = jnp.pad(val_a, pad), jnp.pad(val_b, pad)
        ver_a, ver_b = jnp.pad(ver_a, pad[0]), jnp.pad(ver_b, pad[0])
    grid = (mp // bm, np_ // bn)
    ra = ver_a.reshape(mp, 1)
    rb = ver_b.reshape(mp, 1)

    out_val, out_ver = pl.pallas_call(
        _merge_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((mp, np_), val_a.dtype),
            jax.ShapeDtypeStruct((mp, 1), jnp.int32),
        ],
        interpret=interpret,
    )(val_a, ra, val_b, rb)
    return out_val[:m, :n], out_ver[:m, 0]
