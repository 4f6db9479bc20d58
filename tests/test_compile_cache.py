"""The entry points' compile cache: JAX's own variable wins, else a fixed path."""
import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.launch import compile_cache


@pytest.fixture
def restore_cache_config():
    saved = {
        k: getattr(jax.config, k)
        for k in (
            "jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes",
        )
    }
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def test_env_variable_is_left_to_jax(monkeypatch, tmp_path, restore_cache_config):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == tmp_path
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_a_fixed_dir_that_fills(monkeypatch, tmp_path, restore_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    target = tmp_path / ".jax_cache"
    monkeypatch.setattr(compile_cache, "DEFAULT_CACHE_DIR", target)
    assert compile_cache.cache_entries(target) == 0
    compilation_cache.reset_cache()
    assert compile_cache.enable_compile_cache() == target
    assert jax.config.jax_compilation_cache_dir == str(target)
    # Cache every program, however quick to compile, for this check.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.jit(lambda x: jnp.sin(x) * 3.0 + 1.25)(jnp.arange(7.0)).block_until_ready()
    assert compile_cache.cache_entries(target) > 0


def test_default_path_is_in_the_checkout():
    path = compile_cache.DEFAULT_CACHE_DIR
    assert path.name == ".jax_cache"
    assert (path.parent / "src" / "repro" / "launch" / "compile_cache.py").is_file()
