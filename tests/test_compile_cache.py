"""The entry points' persistent compile cache helper."""
import jax
import pytest

from repro import compile_cache


@pytest.fixture
def cache_config():
    prev = jax.config.jax_compilation_cache_dir
    prev_min = jax.config.jax_persistent_cache_min_compile_time_secs
    yield
    jax.config.update("jax_compilation_cache_dir", prev)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", prev_min)


def test_env_dir_is_left_to_jax(monkeypatch, cache_config, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_dir_is_in_the_checkout(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    d = compile_cache.enable_compile_cache()
    assert d == str(compile_cache.CACHE_DIR)
    assert compile_cache.CACHE_DIR.parent.joinpath("pyproject.toml").exists()
    assert jax.config.jax_compilation_cache_dir == d
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
