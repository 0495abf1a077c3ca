"""JAX's persistent compilation cache, placed from outside.

``enable_compile_cache()`` is the one call every entry point makes:

* if ``JAX_COMPILATION_CACHE_DIR`` is set, that directory is the cache and
  nothing else is set;
* otherwise the cache is ``.jax_cache/`` at the root of the checkout — one
  fixed path (it is part of the cache key's reach: a directory that moves
  never hits), listed in ``.gitignore``.

No path is derived from a temporary name, a process id or the time.
"""
from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["CACHE_DIR", "enable_compile_cache"]

# the fixed in-checkout location (src/repro/utils/cache.py -> the root)
CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; -> its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
