"""Where the persistent compilation cache goes (launch/compile_cache.py)."""
import pathlib

import jax

from repro.launch.compile_cache import CHECKOUT_CACHE, enable_compile_cache


def test_cache_dir_from_environment_or_checkout(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    try:
        # set from outside: JAX reads the variable itself, nothing is set
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert enable_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == before
        # unset: a fixed, gitignored path inside the checkout
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert enable_compile_cache() == str(CHECKOUT_CACHE)
        assert jax.config.jax_compilation_cache_dir == str(CHECKOUT_CACHE)
        root = pathlib.Path(__file__).resolve().parents[1]
        assert CHECKOUT_CACHE == root / ".jax_cache"
        assert ".jax_cache/" in (root / ".gitignore").read_text().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
