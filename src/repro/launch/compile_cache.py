"""JAX's persistent compilation cache for the entry points that compile.

``chip_smoke.py``, ``repro.launch.serve`` and ``benchmarks.run`` call
:func:`use_compile_cache` before their first compile, so that a later run
of the same programs loads them instead of compiling again.  Tests leave
the cache off.
"""
from __future__ import annotations

import os
import pathlib

import jax

# <checkout>/src/repro/launch/compile_cache.py -> <checkout>
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here.  Otherwise the cache is ``<checkout>/.jax_cache``:
    a fixed path, because a cache whose directory moves is never found."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
