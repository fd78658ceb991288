"""JAX's persistent compilation cache, placed from outside the library.

Entry points (chip_smoke.py, benchmarks/run.py, the examples) call
`enable_compile_cache()` before their first compile; no library module
does so on import, so tests and compile-only runs stay cache-free.
"""
from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["enable_compile_cache"]

# the checkout's root: src/repro/launch/compile_cache.py -> parents[3]
_DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent cache and return its directory.

    Where `JAX_COMPILATION_CACHE_DIR` is set, JAX has already read it and
    nothing is set here. Otherwise the cache goes to `.jax_cache/` at the
    root of the checkout: a fixed path, since a directory that moves
    between runs never hits.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(_DEFAULT_DIR))
    return str(_DEFAULT_DIR)
