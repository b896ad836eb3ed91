"""Where JAX keeps its persistent compilation cache.

A cold chip run compiles every step program of a 28-layer model; the
persistent cache lets a later process reuse those executables.  Its
directory is part of what a later run must find again, so it never moves:
``JAX_COMPILATION_CACHE_DIR`` when that is set (JAX reads it itself and
nothing here overrides it), otherwise ``<checkout>/.jax_cache``, which
``.gitignore`` lists.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get(ENV)
    if not path:
        path = str(CHECKOUT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
