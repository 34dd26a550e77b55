"""Where JAX keeps its persistent compilation cache for this repository's
entry points.

The scripts a user runs (``chip_smoke.py``, ``benchmarks/``, ``examples/``)
call :func:`enable_compile_cache` first thing in ``main``. The library never
calls it on import, and the tests never call it: a library that picks a
cache directory on import would override its caller's choice.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: the default: one fixed directory inside the checkout (listed in
#: .gitignore). The path is part of the cache key, so a directory that
#: moves between runs (a temp, pid or time path) would never hit.
CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    this sets nothing. Otherwise the cache goes to :data:`CACHE_DIR`."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
