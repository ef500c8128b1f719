"""The persistent compile cache is placed from outside: the helper sets a
path only where ``JAX_COMPILATION_CACHE_DIR`` does not, and that path is
fixed inside the checkout (the directory is part of the cache key)."""
import os

import jax
import pytest

from deeplearning4j_tpu.util import compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_config():
    """Hand the test jax's cache setting and restore it afterwards — the
    suite itself runs with the cache off."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_var_wins_and_nothing_is_set_in_code(monkeypatch, cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == "/somewhere/else"
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_one_fixed_path_in_the_checkout(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compile_cache.enable_compile_cache()
    assert first == os.path.join(ROOT, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first
    assert compile_cache.enable_compile_cache() == first


def test_cache_dir_is_git_ignored():
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
