"""``repro.compile_cache``: where entry points keep JAX's persistent cache."""

import os

import jax
import pytest

from repro import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_config():
    """Restore the process's cache directory after the test."""
    was = jax.config.jax_compilation_cache_dir
    yield was
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_dir_is_used_and_nothing_is_set(cache_config, monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == cache_config


def test_without_env_the_cache_is_the_fixed_checkout_dir(cache_config, monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert compile_cache.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert compile_cache.enable_compile_cache() == want  # same path every call
