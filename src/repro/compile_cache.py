"""JAX's persistent compilation cache for the command-line entry points.

Call :func:`enable_compile_cache` from a ``__main__`` path, never at import:
a library import (and so every test) leaves the cache as JAX configured it.
"""

from __future__ import annotations

import os
from pathlib import Path

# the checkout root (src/repro/compile_cache.py → ../..); a fixed path,
# because a cache directory that moves between runs never hits
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_compile_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    keeps its cache there; no other path is set. Otherwise the cache goes
    to ``.jax_compile_cache/`` at the root of the checkout.
    """
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
