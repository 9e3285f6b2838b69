"""JAX's persistent compilation cache at one fixed place.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``,
``benchmarks/bench_serve.py``) call ``enable_compile_cache()`` before they
compile anything. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and nothing is set here. Otherwise the cache goes to ``.jax_cache``
at the repository root: a fixed path, because the path is part of the
cache key — a directory that moved with the process (a temp dir, a pid or
a timestamp) would never hit.
"""
from __future__ import annotations

import os

CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at ``CACHE_DIR`` unless
    ``JAX_COMPILATION_CACHE_DIR`` already names one; returns the
    directory in effect."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
