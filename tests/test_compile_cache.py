"""The entry points' compile-cache location (``repro.launch.compile_cache``)."""

import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_dir_is_left_alone(monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_dir_is_fixed_in_the_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable()
    assert path == str(compile_cache.CHECKOUT / ".jax_cache")
    assert (compile_cache.CHECKOUT / "chip_smoke.py").exists()
    assert jax.config.jax_compilation_cache_dir == path
    assert compile_cache.enable() == path  # no pid / time in the name
