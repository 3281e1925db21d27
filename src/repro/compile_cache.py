"""JAX's persistent compilation cache, kept at one fixed place.

A cold run of the solve path spends much of its time compiling, so every
entry point (``chip_smoke.py``, ``benchmarks/run.py``, ``examples/``) turns
the cache on before its first compile.  Where ``JAX_COMPILATION_CACHE_DIR``
is set, JAX reads it itself and nothing is set here.  Otherwise the cache
lives in ``.jax_cache`` at the root of the checkout.  The directory is part
of what a later run must find, so it is never a temporary name.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: Cache directory used when ``JAX_COMPILATION_CACHE_DIR`` is unset.
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
