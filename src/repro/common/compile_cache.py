"""JAX persistent compilation cache placement.

A TPU compile of the archive's kernels and their XLA glue takes seconds per
shape; the persistent cache lets later processes on the same machine load
it instead.  JAX keys its entries by, among other things, the directory, so
the directory must not move between runs: where ``JAX_COMPILATION_CACHE_DIR``
is set, JAX reads it by itself and this module sets nothing; otherwise the
cache goes to ``.jax_cache/`` at the root of the checkout (git-ignored).
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CACHE_DIR", "enable_compile_cache"]

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on before the first compile; returns the
    directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
