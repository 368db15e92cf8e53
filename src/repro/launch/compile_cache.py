"""Where JAX keeps its persistent compilation cache.

A directory that moves between runs (a temporary, or one named by pid or
time) never finds what an earlier run stored: keep it at one fixed place
per checkout.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT_CACHE", "use_compile_cache"]

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself, so
    nothing is set here.  Otherwise the cache is ``<checkout>/.jax_cache``.
    """
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
