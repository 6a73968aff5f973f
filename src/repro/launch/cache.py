"""Where JAX keeps its persistent compilation cache.

Every entry point calls ``use_compile_cache()`` before it compiles.  The
cache key includes the directory, so the directory is fixed: the one
``JAX_COMPILATION_CACHE_DIR`` names when it is set, else ``.jax_cache``
at the root of the checkout (listed in ``.gitignore``)."""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent cache at its one directory; returns it."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CHECKOUT_CACHE)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
