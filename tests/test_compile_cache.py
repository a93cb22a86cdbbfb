"""Placement of JAX's persistent compilation cache
(:func:`repro.utils.compile_cache.enable_compile_cache`)."""

import jax
import pytest

from repro.utils.compile_cache import CACHE_ENV, enable_compile_cache


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_cache_lands_in_env_dir(monkeypatch, tmp_path, restore_cache_dir):
    env_dir = tmp_path / "from_env"
    monkeypatch.setenv(CACHE_ENV, str(env_dir))
    assert enable_compile_cache(tmp_path / "checkout") == str(env_dir)
    assert jax.config.jax_compilation_cache_dir == str(env_dir)


def test_cache_lands_at_fixed_checkout_path(monkeypatch, tmp_path,
                                            restore_cache_dir):
    monkeypatch.delenv(CACHE_ENV, raising=False)
    want = str(tmp_path.resolve() / ".jax_cache")
    assert enable_compile_cache(tmp_path) == want
    assert jax.config.jax_compilation_cache_dir == want
    # the same checkout always maps to the same directory
    assert enable_compile_cache(tmp_path) == want
