"""Where the entry points put JAX's persistent compilation cache."""
import os

import jax

from repro.launch.compile_cache import enable_compile_cache

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def test_env_cache_dir_is_left_to_jax(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_cache_dir_is_fixed_in_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = enable_compile_cache()
        assert path == os.path.join(REPO_ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert enable_compile_cache() == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
