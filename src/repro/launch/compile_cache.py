"""Where the entry points keep JAX's persistent compilation cache.

A cache is only found again at the same path, so the path is fixed: the
directory named by ``JAX_COMPILATION_CACHE_DIR`` when that is set (JAX
reads the variable itself), otherwise ``<checkout>/.jax_cache``, which
``.gitignore`` lists.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def configure_compile_cache() -> str:
    """Point the persistent compile cache at its fixed place and return
    that directory. An env-given directory is left to JAX as it is."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
