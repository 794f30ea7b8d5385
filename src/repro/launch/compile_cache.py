"""Where JAX's persistent compilation cache lives.

Every entry point calls ``enable_compile_cache()`` before its first
compile.  The cache's key includes its directory, so the directory is
fixed: ``$JAX_COMPILATION_CACHE_DIR`` when the caller sets it (JAX reads
that variable itself, and nothing else is set here), otherwise
``<checkout>/.jax_cache``, which git ignores.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
