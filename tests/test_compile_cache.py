"""``repro.compile_cache``: one cache location, never two."""
import os

import jax
import pytest

from repro.compile_cache import CHECKOUT_CACHE, enable_compile_cache


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_dir_wins_and_code_sets_none(monkeypatch, restore_cache_dir, tmp_path):
    jax.config.update("jax_compilation_cache_dir", "/set/by/someone/else")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == "/set/by/someone/else"


def test_default_is_checkout_dir_from_any_cwd(monkeypatch, restore_cache_dir, tmp_path):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.chdir(tmp_path)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert enable_compile_cache() == CHECKOUT_CACHE == os.path.join(root, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == CHECKOUT_CACHE
