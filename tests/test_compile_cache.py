"""`repro.compile_cache`: where the entry points keep JAX's compile cache."""

import os

import jax
import pytest

from repro.compile_cache import CACHE_DIRNAME, use_compile_cache


@pytest.fixture
def restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_cache_defaults_to_fixed_dir_in_checkout(tmp_path, monkeypatch,
                                                 restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = use_compile_cache(str(tmp_path))
    assert path == os.path.join(str(tmp_path), CACHE_DIRNAME)
    assert jax.config.jax_compilation_cache_dir == path
    # the same checkout always maps to the same directory (a cache key)
    assert use_compile_cache(str(tmp_path)) == path


def test_cache_follows_environment_when_set(tmp_path, monkeypatch,
                                            restore_cache_dir):
    env_dir = str(tmp_path / "from-env")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    before = jax.config.jax_compilation_cache_dir
    assert use_compile_cache(str(tmp_path)) == env_dir
    # JAX reads the variable itself; the helper sets nothing of its own
    assert jax.config.jax_compilation_cache_dir == before
