"""Placement of JAX's persistent compilation cache, for entry-point scripts.

Library code never calls this: importing ``repro`` changes no global JAX
setting.  ``chip_smoke.py`` and ``benchmarks/run.py`` call
:func:`enable_compile_cache` once, before their first compile.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

# <checkout>/.jax_cache: a fixed path, because the cache directory is part
# of what a later process must find again (a temp, pid or time-stamped
# directory would never hit).  Listed in .gitignore.
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    it is left in place: no other directory is set.  Otherwise the cache
    goes to :data:`CHECKOUT_CACHE_DIR`.
    """
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
