"""Compile-cache placement of the launch environment: an outside
``JAX_COMPILATION_CACHE_DIR`` wins, else a fixed path in the checkout."""

import os

import jax
import pytest

from repro.launch import env


@pytest.fixture
def cache_dir_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_cache_dir_from_environment_is_not_overridden(
        monkeypatch, tmp_path, cache_dir_config):
    monkeypatch.setenv(env.CACHE_ENV, str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", None)
    assert env.use_compile_cache() == str(tmp_path)
    # JAX reads the variable itself; nothing was set in code
    assert jax.config.jax_compilation_cache_dir is None


def test_cache_dir_defaults_to_fixed_checkout_path(monkeypatch,
                                                   cache_dir_config):
    monkeypatch.delenv(env.CACHE_ENV, raising=False)
    path = env.use_compile_cache()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert path == os.path.join(root, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
