"""Where the persistent compilation cache goes."""
from pathlib import Path

import jax
import pytest

from repro.launch.compile_cache import use_compile_cache

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture()
def cache_dir_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_unset_env_puts_the_cache_in_the_checkout(monkeypatch,
                                                  cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert use_compile_cache() == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == str(ROOT / ".jax_cache")


def test_env_var_is_left_to_jax(monkeypatch, cache_dir_config, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
