"""JAX's persistent compilation cache for the entry points.

Every entry point (``launch.serve``, ``launch.train``, ``benchmarks.run``,
``chip_smoke.py``) calls :func:`enable_compile_cache` once before it
compiles. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it
and nothing is changed. Otherwise the cache goes to ``.jax_cache`` at the
root of the checkout: a fixed path, because the path is part of what makes a
later process find an entry again.
"""

from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["CHECKOUT_CACHE", "enable_compile_cache"]

CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns
    the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
