"""Where JAX keeps its persistent compilation cache.

Entry points call :func:`use_compile_cache` from ``main()``; importing this
module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# The checkout root (src/repro/launch/ -> three levels up). A fixed path:
# the cache directory is part of each entry's key, so a directory built
# from a temp name, pid or time would never hit.
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and no
    other directory is set here; otherwise the cache lives in
    ``<checkout>/.jax_cache``.
    """
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
