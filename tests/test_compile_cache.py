"""The persistent compilation cache goes where the environment says, or to
one fixed directory in the checkout, and only when an entry point asks."""
import importlib
import os

import jax
import pytest

from repro import compile_cache

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


@pytest.fixture
def cache_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_default_is_fixed_dir_in_checkout(cache_config, monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    path = compile_cache.configure()
    assert path == os.path.join(ROOT, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert compile_cache.configure() == path      # the same on every call


def test_environment_wins_and_nothing_else_is_set(cache_config, monkeypatch,
                                                  tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", None)
    assert compile_cache.configure() == str(tmp_path)
    # JAX reads the variable itself; the code sets no directory of its own
    assert jax.config.jax_compilation_cache_dir is None


def test_import_sets_nothing(cache_config):
    jax.config.update("jax_compilation_cache_dir", None)
    importlib.reload(compile_cache)
    assert jax.config.jax_compilation_cache_dir is None
