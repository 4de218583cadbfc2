"""Placement of JAX's persistent compilation cache by the entry points."""
import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def restore_cache_dir():
    saved = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", saved)


def test_placed_from_outside_sets_nothing(monkeypatch, restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_fixed_repo_path_otherwise(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    placed = compile_cache.enable_compile_cache()
    assert placed == str(compile_cache.REPO_CACHE_DIR)
    assert compile_cache.REPO_CACHE_DIR.parent.joinpath("pyproject.toml").is_file()
    assert compile_cache.REPO_CACHE_DIR.name == ".jax_cache"
    assert jax.config.jax_compilation_cache_dir == placed
    assert compile_cache.enable_compile_cache() == placed  # same path every call
