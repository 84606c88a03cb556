"""Where the entry points keep JAX's persistent compilation cache."""

from pathlib import Path

import jax
import pytest

from repro.compile_cache import enable_compile_cache

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_dir_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_cache_honours_the_environment(monkeypatch, cache_dir_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    was = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == was   # nothing set


def test_cache_defaults_to_a_fixed_checkout_path(monkeypatch,
                                                 cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = str(REPO / ".jax_cache")
    assert enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert enable_compile_cache() == want        # same path every call
