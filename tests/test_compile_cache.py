"""The launch entry points' persistent compilation cache: the directory
``JAX_COMPILATION_CACHE_DIR`` names when it is set, else a fixed
``<repo>/.jax_cache`` that git ignores."""
from pathlib import Path

import jax
import pytest

from repro.launch.compile_cache import REPO_CACHE_DIR, enable_compile_cache

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_dir_config():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_env_dir_is_used_and_nothing_is_set(monkeypatch, cache_dir_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/cache")
    jax.config.update("jax_compilation_cache_dir", None)
    assert enable_compile_cache() == "/some/cache"
    assert jax.config.jax_compilation_cache_dir is None


def test_default_is_fixed_repo_dir(monkeypatch, cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert enable_compile_cache() == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == str(REPO_CACHE_DIR)
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().splitlines()
