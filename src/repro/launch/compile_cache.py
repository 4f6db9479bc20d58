"""JAX's persistent compilation cache, kept at one fixed directory.

The entry points (``chip_smoke.py``, ``examples/tsunami_inversion.py``,
``repro.launch.serve``, ``repro.launch.export``) call
:func:`enable_compile_cache` before they compile anything, so a second run
of the same programs loads them instead of compiling again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/.jax_cache: this file is <checkout>/src/repro/launch/compile_cache.py
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> Path:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    other path is set here.  Otherwise the cache goes to ``.jax_cache`` at
    the root of the checkout.  The path holds no temporary name, pid or
    time: a cache that moves between runs is never hit.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return Path(env)
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return DEFAULT_CACHE_DIR


def cache_entries(path: Path) -> int:
    """Number of entries in a cache directory (0 if it does not exist yet)."""
    return len(os.listdir(path)) if path.is_dir() else 0
