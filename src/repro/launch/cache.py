"""JAX's persistent compilation cache, for the entry points.

A full-width serve compiles for minutes; the cache lets a later process
on the same checkout skip that.  Called from entry points only
(``chip_smoke.py``, ``repro.launch.serve``, ``benchmarks/run.py``),
never at import.
"""
from __future__ import annotations

import os

import jax

# a fixed directory in the checkout: the cache key includes the path, so a
# directory that moves (a temp name, a pid, a timestamp) would never hit
CHECKOUT_CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.
    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set; otherwise the cache goes to ``.jax_cache/`` at
    the root of the checkout."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
