"""The persistent compilation cache is placed from outside: the
``JAX_COMPILATION_CACHE_DIR`` environment variable when it is set, a
fixed directory of the checkout otherwise."""
from pathlib import Path

import jax
import pytest

from repro.launch.compile_cache import DEFAULT_DIR, enable_compile_cache

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_dir_restored():
    old = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", old)


def test_follows_the_environment(monkeypatch, tmp_path, cache_dir_restored):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    # left to JAX, which reads the variable itself: no other directory
    assert jax.config.jax_compilation_cache_dir == before


def test_fixed_checkout_path_otherwise(monkeypatch, cache_dir_restored):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert DEFAULT_DIR == ROOT / ".jax_cache"
    assert enable_compile_cache() == str(DEFAULT_DIR)
    assert jax.config.jax_compilation_cache_dir == str(DEFAULT_DIR)
    # the same path on every call: the directory never moves
    assert enable_compile_cache() == str(DEFAULT_DIR)


def test_cache_dir_is_git_ignored():
    ignored = (ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
