"""Where JAX keeps its persistent compilation cache.

A cold process compiles every kernel and executor program it runs; the
cache lets the next process on the same machine load them instead.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this module
sets nothing.  Otherwise the cache lives at ``<checkout>/.jax_cache`` — a
fixed path, because the path is part of what a later process must find.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory.  Call before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


__all__ = ["enable_compile_cache"]
