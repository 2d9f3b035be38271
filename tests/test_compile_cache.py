"""The entry points' persistent compilation cache: JAX's own variable when
it is set, else a fixed directory in the checkout."""
import pathlib

import jax
import pytest

from repro.launch import cache


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_var_is_honoured(monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.setenv(cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert cache.enable_compile_cache() == str(tmp_path)
    # JAX reads the variable itself; the helper sets no other directory
    assert jax.config.jax_compilation_cache_dir == before


def test_fixed_checkout_path_without_env(monkeypatch, restore_cache_dir):
    monkeypatch.delenv(cache.ENV_VAR, raising=False)
    path = cache.enable_compile_cache()
    checkout = pathlib.Path(__file__).resolve().parents[1]
    assert path == str(checkout / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    # fixed: a second call (another process, a later run) finds the same one
    assert cache.enable_compile_cache() == path
