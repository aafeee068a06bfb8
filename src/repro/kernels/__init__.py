"""Pallas TPU kernels, each with ``<name>.py`` (kernel), ``ops.py`` (wrapper)
and ``ref.py`` (pure-jnp oracle)."""

from __future__ import annotations

import jax

__all__ = ["interpret_mode", "round_up", "sublane_tile"]


def interpret_mode(interpret: bool | None = None) -> bool:
    """Whether a kernel call runs in Pallas interpret mode.

    Decided when the kernel is traced, never at import.  ``None`` means
    "interpret on the CPU backend only".  Interpret mode on any other
    backend is refused: a TPU run must execute the compiled kernel.
    ``False`` is accepted everywhere, so a CPU process can compile a kernel
    for a described TPU.
    """
    backend = jax.default_backend()
    if interpret and backend != "cpu":
        raise ValueError(
            f"Pallas interpret mode requested on the {backend!r} backend; "
            "kernels are only interpreted on the CPU"
        )
    return backend == "cpu" if interpret is None else interpret


def round_up(x: int, k: int) -> int:
    return -(-x // k) * k


def sublane_tile(dtype) -> int:
    """Rows of one (rows, 128) VMEM tile: 8 for 32-bit, 16 for 16-bit, 32
    for 8-bit dtypes.  Block shapes are multiples of it and of 128 lanes."""
    return 8 * max(1, 4 // jax.numpy.dtype(dtype).itemsize)
