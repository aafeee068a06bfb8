"""Where the entry points keep JAX's persistent compilation cache.

A cache is found again only at the path it was written to, so the path is
fixed: ``$JAX_COMPILATION_CACHE_DIR`` when set (JAX reads that variable
itself, and nothing is set here), else ``<repo root>/.jax_cache``.
"""

from __future__ import annotations

import os
from pathlib import Path

__all__ = ["REPO_CACHE_DIR", "compile_cache_dir", "enable_compile_cache"]

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> str:
    """The directory the persistent compilation cache lives in."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(REPO_CACHE_DIR)


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at
    :func:`compile_cache_dir`; returns that directory."""
    path = compile_cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        import jax

        jax.config.update("jax_compilation_cache_dir", path)
    return path
