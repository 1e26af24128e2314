"""The entry points' persistent compilation cache location
(launch/compile_cache.py): the environment's directory when given,
else one fixed directory inside the checkout."""
import os

import jax
import pytest

from repro.launch import compile_cache as CC

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_directory_wins_and_nothing_is_set(monkeypatch,
                                               restore_cache_dir):
    monkeypatch.setenv(CC.ENV_VAR, "/elsewhere/cache")
    jax.config.update("jax_compilation_cache_dir", None)
    assert CC.enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir is None


def test_default_is_fixed_directory_in_checkout(monkeypatch,
                                                restore_cache_dir):
    monkeypatch.delenv(CC.ENV_VAR, raising=False)
    path = CC.enable_compile_cache()
    assert path == os.path.join(ROOT, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert CC.enable_compile_cache() == path        # stable across calls
