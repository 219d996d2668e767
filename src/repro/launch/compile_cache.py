"""Persistent XLA compile cache for the entry points.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets nothing.  Otherwise every entry point (``chip_smoke.py``,
``repro.launch.select``, ``repro.launch.select_serve``) keeps the cache at
one fixed path inside the checkout, ``<checkout>/.jax_cache`` (listed in
``.gitignore``): the directory is part of the cache key, so a path that
moved between runs would never hit.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: ``<checkout>/.jax_cache`` — this file is <checkout>/src/repro/launch/.
CHECKOUT_CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on before the first compile and
    return the directory it writes to."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
