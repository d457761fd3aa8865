"""Where entry points keep JAX's persistent compilation cache.

The cache is keyed by the directory's path among other things, so it lives
at a fixed place: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it
(JAX reads that variable itself, and nothing here overrides it), otherwise
``<checkout>/.jax_cache``. Tests do not call this.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
