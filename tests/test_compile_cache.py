"""`repro.compile_cache.enable`: the persistent compilation cache is placed
by ``JAX_COMPILATION_CACHE_DIR`` when set (nothing set in code), else at
the fixed checkout-local ``.jax_cache``."""
import jax
import pytest

from repro import compile_cache


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_var_wins_and_nothing_is_set(monkeypatch, restore_cache_dir):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV_VAR, "/somewhere/else")
    assert compile_cache.enable() == "/somewhere/else"
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_fixed_inside_the_checkout(monkeypatch,
                                              restore_cache_dir):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    path = compile_cache.enable()
    assert path == str(compile_cache.DEFAULT_DIR)
    assert compile_cache.DEFAULT_DIR.parent.joinpath("src").is_dir()
    assert jax.config.jax_compilation_cache_dir == path
    assert compile_cache.enable() == path        # stable across calls
