"""Pallas TPU kernel: RWKV-6 WKV recurrence, time-chunked with VMEM-resident
state.

TPU adaptation (vs the CUDA wkv6 kernel): the GPU version assigns one thread
per (batch, head, channel) and serializes over T in registers; on TPU we keep
the whole (N, N) per-head state as a VMEM scratch tile and sweep time in
chunks.  The grid is (B*H, T / tc) with ``dimension_semantics=("parallel",
"arbitrary")``: time iterates innermost, so the scratch state persists across
one head's chunks and is re-initialized at chunk 0.

Per chunk, an inner fori_loop performs tc rank-1 updates on the state tile
(VPU ops on an (N, N) tile; N=64 head dims round up to the 128-lane register
width).  HBM traffic is O(T*N) in/out; the O(T*N^2) kv outer products never
leave VMEM — that is the kernel's point.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .. import interpret_mode

DEFAULT_TIME_CHUNK = 128


def _wkv6_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref,
                 y_ref, sfin_ref, state):
    tc = r_ref.shape[1]

    @pl.when(pl.program_id(1) == 0)
    def _init():
        state[...] = s0_ref[0]

    def step(t, carry):
        rt = r_ref[0, t, :]                     # (N,)
        kt = k_ref[0, t, :]
        vt = v_ref[0, t, :]
        wt = w_ref[0, t, :]
        u = u_ref[0, 0, :]
        s = state[...]                          # (N, N)
        kv = kt[:, None] * vt[None, :]          # (N, N)
        y = (rt[:, None] * (s + u[:, None] * kv)).sum(axis=0)   # (N,)
        y_ref[0, t, :] = y
        state[...] = wt[:, None] * s + kv
        return carry

    jax.lax.fori_loop(0, tc, step, 0)

    @pl.when(pl.program_id(1) == pl.num_programs(1) - 1)
    def _fin():
        sfin_ref[0] = state[...]


@functools.partial(jax.jit, static_argnames=("time_chunk", "interpret"))
def wkv6_pallas(
    r: jnp.ndarray,     # (BH, T, N) float32
    k: jnp.ndarray,
    v: jnp.ndarray,
    w: jnp.ndarray,
    u: jnp.ndarray,     # (BH, N); a (BH, 1, N) block keeps the tiling legal
    s0: jnp.ndarray,    # (BH, N, N)
    *,
    time_chunk: int = DEFAULT_TIME_CHUNK,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    from jax.experimental.pallas import tpu as pltpu

    interpret = interpret_mode(interpret)
    bh, t, n = r.shape
    tc = min(time_chunk, t)
    while t % tc:
        tc -= 1
    grid = (bh, t // tc)

    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        )
    return pl.pallas_call(
        _wkv6_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tc, n), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, tc, n), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, tc, n), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, tc, n), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, n), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, n, n), lambda i, j: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, tc, n), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, n, n), lambda i, j: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, n), jnp.float32),
            jax.ShapeDtypeStruct((bh, n, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, n), jnp.float32)],
        interpret=interpret,
        **kwargs,
    )(r, k, v, w, u.reshape(bh, 1, n), s0)
