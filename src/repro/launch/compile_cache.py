"""JAX persistent compilation cache at a fixed place.

The cache key includes the cache directory, so a directory that moves from
run to run never hits.  ``enable_compile_cache`` therefore leaves a
``JAX_COMPILATION_CACHE_DIR`` from the environment to JAX (which reads it
itself) and otherwise points JAX at ``.jax_cache/`` in the checkout root
(listed in ``.gitignore``) — never a temporary, per-process or timed name.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"

#: the checkout root: src/repro/launch/compile_cache.py → parents[3]
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    path = os.environ.get(ENV)
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
