"""Persistent XLA compilation cache placement.

A cold max-flow solve at 10^6 vertices spends tens of seconds compiling
its cycle and sweep loops; JAX's persistent compilation cache lets the
next process skip that.  The cache key includes the directory, so the
directory must not move between runs: it is ``JAX_COMPILATION_CACHE_DIR``
when the environment sets it, and otherwise the fixed ``.jax_cache``
directory at the root of the checkout (listed in ``.gitignore``).

Entry points call :func:`enable_compile_cache` first thing in ``main``;
nothing configures the cache at import time.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: ``<checkout>/.jax_cache`` — src/repro/runtime/cache.py is three levels
#: below the checkout root
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory and
    return that directory: ``$JAX_COMPILATION_CACHE_DIR`` if set, else
    :data:`DEFAULT_DIR`."""
    import jax

    path = os.environ.get(ENV_VAR) or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
