"""The persistent compilation cache: one directory rule for every entry point."""
from __future__ import annotations

import jax
import pytest

from repro import compile_cache


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_dir_is_left_alone(monkeypatch, restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/x")
    jax.config.update("jax_compilation_cache_dir", "/x")   # what JAX read
    assert compile_cache.enable_compile_cache() == "/x"
    assert jax.config.jax_compilation_cache_dir == "/x"


def test_unset_env_uses_the_fixed_repo_dir(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = compile_cache.enable_compile_cache()
    repo = compile_cache.DEFAULT_CACHE_DIR.parent
    assert got == str(repo / ".jax_cache")
    assert (repo / "pyproject.toml").exists()
    assert jax.config.jax_compilation_cache_dir == got
    # the same path in every process: nothing derived from time, pid or tmp
    assert compile_cache.enable_compile_cache() == got
