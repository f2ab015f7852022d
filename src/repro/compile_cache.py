"""Where JAX keeps its persistent compilation cache.

Entry points call ``use_compile_cache()`` before they compile anything.
When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
leaves it alone. Otherwise the cache goes to ``.jax_cache`` at the root
of the checkout: a fixed path, because the directory is part of what a
later run must find again (git ignores it)."""

from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    if os.environ.get(CACHE_ENV):
        return os.environ[CACHE_ENV]
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
