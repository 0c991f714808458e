"""The persistent compile-cache helper: JAX_COMPILATION_CACHE_DIR when it
is set, else one fixed directory at the checkout root."""
from pathlib import Path

import jax
import pytest

from repro.kernels.backend import CHECKOUT_CACHE_DIR, enable_compile_cache

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture
def restore_cache_dir():
    # Nothing compiles between the helper's call and this restore, so the
    # worker's cache state is untouched for later tests.
    old = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", old)


def test_compile_cache_honours_env_dir(monkeypatch, tmp_path,
                                       restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    # JAX reads the variable itself; the helper configures nothing else.
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_fixed_checkout_dir(monkeypatch,
                                                      restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = enable_compile_cache()
    assert first == enable_compile_cache() == str(CHECKOUT_CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == first
    assert CHECKOUT_CACHE_DIR == REPO / ".jax_cache"
    ignored = (REPO / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
