"""Where the entry points keep JAX's persistent compilation cache."""
import os

import jax

from repro.launch import compile_cache


def test_env_dir_wins(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.compile_cache_dir() == str(tmp_path)


def test_default_is_one_fixed_path_in_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compile_cache.compile_cache_dir()
    assert first == compile_cache.compile_cache_dir()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert first == os.path.join(repo, ".jax_cache")


def test_enable_sets_jax_config(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert compile_cache.enable_compile_cache() \
            == compile_cache.REPO_CACHE_DIR
        assert jax.config.jax_compilation_cache_dir \
            == compile_cache.REPO_CACHE_DIR
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
