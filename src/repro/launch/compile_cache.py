"""JAX's persistent compilation cache, placed from outside.

Entry points (``launch/train.py``, ``launch/serve.py``, ``chip_smoke.py``
and the benchmark scripts) call :func:`enable_compile_cache` once, before
their first compile.  Nothing calls it at import time, so a library
import or a test process never changes where JAX caches.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# one fixed directory inside the checkout (git-ignored): a path built
# from a temp name, a pid or the time would never be found again
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it
    and no other directory is set here.  Otherwise the cache lives at
    :data:`DEFAULT_CACHE_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
