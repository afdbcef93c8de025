"""JAX's persistent compilation cache, for the program's entry points.

The entry points (``chip_smoke.py``, ``repro.launch.convert``, the
benchmarks) call :func:`enable_compile_cache` first thing, so a compile
done by one process is found again by the next. Importing the library
never turns it on.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["enable_compile_cache", "REPO_CACHE"]

#: the checkout's own cache directory (git-ignored). A fixed path: the path
#: is part of each entry's key, so a directory that moves never hits.
REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    no other directory is set. Otherwise the cache is ``.jax_cache/`` at
    the root of the checkout.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
