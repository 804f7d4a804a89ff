"""Where JAX's persistent compilation cache lives — the one place that
decides. The crypto kernels are large programs (tens of seconds each to
compile for a TPU), so every entry point that starts JAX (replica
processes, benchmarks, chip_smoke.py, the tests) calls `setup_cache()`
before its first compile.

The directory is part of the cache key, so it never moves: whatever
`JAX_COMPILATION_CACHE_DIR` names when it is set (JAX reads that
variable itself — nothing is set in code then), else `.jax_cache` at
the root of the checkout. Never a temp name, a pid or a time.
"""
from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def setup_cache() -> str:
    """Place the compile cache; returns the directory in use."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
