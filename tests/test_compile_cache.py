"""The persistent compilation cache lives at a fixed place: where
``JAX_COMPILATION_CACHE_DIR`` says, else ``<checkout>/.jax_cache``."""
import os

import jax
import pytest

from repro import compile_cache


@pytest.fixture
def jax_cache_config():
    """Restore the two settings the helper may change."""
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def test_env_dir_stands_and_nothing_is_set(monkeypatch, jax_cache_config,
                                           tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_dir_is_the_checkouts(monkeypatch, jax_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert path == os.path.join(root, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert compile_cache.enable_compile_cache() == path   # never moves
