"""The entry points' persistent compilation cache location."""

import os

import jax

from repro.launch.compile_cache import DEFAULT_CACHE_DIR, enable_compile_cache

CHECKOUT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def test_env_dir_is_used_and_nothing_set(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_dir_is_fixed_in_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = enable_compile_cache()
        assert path == os.path.join(CHECKOUT, ".jax_cache")
        assert path == str(DEFAULT_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
