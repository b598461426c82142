"""The persistent compilation cache is placed from outside when it can be:
``JAX_COMPILATION_CACHE_DIR`` wins, else the fixed in-checkout directory
(never a temporary name), which ``.gitignore`` lists."""
import os

import jax
import pytest

from repro.launch import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_dir_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_var_directory_is_left_to_jax(monkeypatch, cache_dir_config):
    monkeypatch.setenv(compile_cache.ENV_VAR, "/srv/jax-cache")
    # JAX reads the variable itself at start-up
    jax.config.update("jax_compilation_cache_dir", "/srv/jax-cache")
    assert compile_cache.setup_compile_cache() == "/srv/jax-cache"
    assert jax.config.jax_compilation_cache_dir == "/srv/jax-cache"


def test_unset_env_uses_fixed_checkout_directory(monkeypatch,
                                                 cache_dir_config):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    path = compile_cache.setup_compile_cache()
    assert path == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert compile_cache.setup_compile_cache() == path     # stable
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
