"""JAX's persistent compilation cache, placed from outside the program.

The entry points (``chip_smoke.py``, ``launch/serve.py``,
``launch/train.py``) call :func:`enable_compile_cache` once, before their
first compile.  Nothing here runs at import time.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
sets nothing.  Otherwise the cache goes to ``<checkout>/.jax_cache``, a
fixed path (listed in ``.gitignore``): a directory that moved between
runs, named from a temp dir, a pid or the time, would never hit.
"""

from __future__ import annotations

import os

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
# src/repro/launch/compile_cache.py -> the checkout root
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get(ENV)
    if path:
        return path
    os.makedirs(DEFAULT_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
