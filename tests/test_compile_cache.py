"""``launch/compile_cache.py``: where the entry points put JAX's persistent
compilation cache."""
import os

import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def cache_dir_config():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_var_set_is_left_alone(monkeypatch, tmp_path, cache_dir_config):
    """JAX reads JAX_COMPILATION_CACHE_DIR itself; the helper neither
    overrides nor repeats it."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_env_var_unset_uses_checkout(monkeypatch, cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    want = os.path.join(root, ".jax_cache")
    assert compile_cache.use_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    # a fixed path, so the next process finds what this one compiled
    assert compile_cache.use_compile_cache() == want
