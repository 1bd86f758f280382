"""repro.compile_cache: where the entry-point scripts keep compiled programs."""
import jax
import pytest

from repro import compile_cache


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_dir_is_used_and_nothing_is_set(monkeypatch, restore_cache_dir):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/cache/from/env")
    assert compile_cache.enable() == "/cache/from/env"
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_the_fixed_checkout_dir(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable()
    assert path == str(compile_cache.CHECKOUT_CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == path
    assert compile_cache.CHECKOUT_CACHE_DIR.parent.joinpath("src").is_dir()
    assert compile_cache.CHECKOUT_CACHE_DIR.name == ".jax_cache"
