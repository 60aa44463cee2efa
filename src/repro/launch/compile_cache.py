"""Persistent XLA compilation cache for the program's entry points.

Call :func:`enable_compile_cache` from a ``main()``, never at import.  A
cache directory named by ``JAX_COMPILATION_CACHE_DIR`` is left to JAX;
otherwise compiled programs go to ``<checkout>/.jax_cache``.  The path is
fixed because it is part of what a later run looks up — a directory that
moves never hits.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
