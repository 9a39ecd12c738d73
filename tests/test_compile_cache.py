"""Where the entry points put JAX's persistent compilation cache."""
import jax
import pytest

from repro import compile_cache


@pytest.fixture
def restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_env_placement_is_left_to_jax(monkeypatch, restore_cache_dir, tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_a_fixed_directory_in_the_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == str(compile_cache.DEFAULT_DIR)
    assert compile_cache.DEFAULT_DIR.parent.joinpath("src", "repro").is_dir()
    assert compile_cache.DEFAULT_DIR.name == ".jax_cache"
    assert jax.config.jax_compilation_cache_dir == path
    assert compile_cache.enable_compile_cache() == path  # stable across calls
