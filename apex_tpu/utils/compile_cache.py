"""Where the persistent XLA compile cache lives.

One rule for every entry point (``chip_smoke.py``,
``examples/train_bert.py``): call :func:`enable_compile_cache` before
the first compile. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
already reads it and nothing is set here, so the cache can be placed
from outside. Otherwise it goes to ``.jax_cache/`` at the root of the
checkout — a fixed path, because the path is part of the cache key: a
temporary directory, a pid or a timestamp would never hit.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns the directory used."""
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
