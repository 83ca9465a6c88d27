"""Where the persistent compilation cache lives (launch/compile_cache.py)."""
from __future__ import annotations

import pathlib

import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def cache_config():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_dir_wins_and_nothing_is_set(monkeypatch, cache_config,
                                         tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", None)
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir is None


def test_unset_env_uses_the_same_checkout_dir(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compile_cache.use_compile_cache()
    assert compile_cache.use_compile_cache() == first
    assert jax.config.jax_compilation_cache_dir == first
    repo = pathlib.Path(__file__).resolve().parents[1]
    assert pathlib.Path(first) == repo / ".jax_cache"
