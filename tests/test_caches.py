"""Where the persistent caches live: fixed paths inside the checkout."""

from pathlib import Path

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache as cc

from repro import caches
from repro.kernels import autotune

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)
    cc.reset_cache()


def test_compile_cache_honours_env(monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert caches.enable_compile_cache() == str(tmp_path)
    # JAX reads the variable itself; the function must not override it.
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_fixed_checkout_path(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = caches.enable_compile_cache()
    assert got == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == got
    # The same path on every call: it is part of the cache key.
    assert caches.enable_compile_cache() == got


def test_autotune_cache_defaults_to_fixed_checkout_path(monkeypatch):
    monkeypatch.delenv(autotune.AUTOTUNE_CACHE_ENV, raising=False)
    assert autotune.autotune_cache_dir() == str(REPO / ".autotune_cache")
    monkeypatch.setenv(autotune.AUTOTUNE_CACHE_ENV, "off")
    assert autotune.autotune_cache_dir() is None
