"""Pallas TPU kernel: RG-LRU gated linear recurrence (Griffin).

Grid: (B, D / bd, T / tc) — batch and channel-blocks are parallel; time is
the innermost (arbitrary) dimension so the (1, bd) state row in VMEM scratch
persists across a channel block's chunks.  Within a chunk the fori_loop walks
tc steps; every step is a fused multiply-add on a (1, bd) register row.

vs GPU: the CUDA linear-scan kernels (e.g. Hawk/Griffin) block over channels
per warp with shuffle-based chunked prefix products; the TPU layout instead
keeps channels lane-aligned (bd a multiple of 128) and trades the log-depth
prefix trick for a short sequential sweep per chunk — the MXU is idle either
way and HBM traffic is identical, so the simple sweep is roofline-neutral.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .. import interpret_mode

DEFAULT_TIME_CHUNK = 256
DEFAULT_CHANNEL_BLOCK = 512


def _rglru_kernel(a_ref, b_ref, h0_ref, h_ref, hfin_ref, state):
    tc = a_ref.shape[1]

    @pl.when(pl.program_id(2) == 0)
    def _init():
        state[...] = h0_ref[0]

    def step(t, carry):
        h = a_ref[0, t, :] * state[0, :] + b_ref[0, t, :]
        h_ref[0, t, :] = h
        state[0, :] = h
        return carry

    jax.lax.fori_loop(0, tc, step, 0)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _fin():
        hfin_ref[0] = state[...]


@functools.partial(jax.jit, static_argnames=("time_chunk", "channel_block", "interpret"))
def rglru_scan_pallas(
    a: jnp.ndarray,      # (B, T, D) float32
    b: jnp.ndarray,
    h0: jnp.ndarray,     # (B, D)
    *,
    time_chunk: int = DEFAULT_TIME_CHUNK,
    channel_block: int = DEFAULT_CHANNEL_BLOCK,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    from jax.experimental.pallas import tpu as pltpu

    interpret = interpret_mode(interpret)
    bsz, t, d = a.shape
    tc = min(time_chunk, t)
    while t % tc:
        tc -= 1
    bd = min(channel_block, d)
    while d % bd:
        bd -= 1
    grid = (bsz, d // bd, t // tc)

    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        )
    # (B, 1, D) state blocks keep the (8, 128) tiling legal for any B
    h, h_fin = pl.pallas_call(
        _rglru_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tc, bd), lambda i, j, k: (i, k, j)),
            pl.BlockSpec((1, tc, bd), lambda i, j, k: (i, k, j)),
            pl.BlockSpec((1, 1, bd), lambda i, j, k: (i, 0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, tc, bd), lambda i, j, k: (i, k, j)),
            pl.BlockSpec((1, 1, bd), lambda i, j, k: (i, 0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, t, d), jnp.float32),
            jax.ShapeDtypeStruct((bsz, 1, d), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, bd), jnp.float32)],
        interpret=interpret,
        **kwargs,
    )(a, b, h0.reshape(bsz, 1, d))
    return h, h_fin.reshape(bsz, d)
