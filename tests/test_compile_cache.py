"""The persistent compilation cache lands where JAX_COMPILATION_CACHE_DIR
says when it is set, and at the checkout's fixed .jax_cache otherwise."""
import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache as cc

from repro import compile_cache


@pytest.fixture
def cache_guard():
    keys = (
        "jax_compilation_cache_dir",
        "jax_enable_compilation_cache",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes",
    )
    before = {k: getattr(jax.config, k) for k in keys}
    cc.reset_cache()
    yield
    for k, v in before.items():
        jax.config.update(k, v)
    cc.reset_cache()


def _listing(path):
    return sorted(p.name for p in path.iterdir()) if path.is_dir() else []


def test_env_dir_gets_the_cache_and_nothing_else_does(monkeypatch, cache_guard, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))  # what JAX reads at start
    before = _listing(compile_cache.DEFAULT_DIR)
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.jit(lambda x: jnp.sin(x) * 3.0 + 0.125)(jnp.arange(7.0)).block_until_ready()
    assert _listing(tmp_path), "nothing was cached in JAX_COMPILATION_CACHE_DIR"
    assert _listing(compile_cache.DEFAULT_DIR) == before


def test_unset_env_uses_the_checkouts_fixed_dir(monkeypatch, cache_guard):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == str(compile_cache.DEFAULT_DIR)
    assert compile_cache.DEFAULT_DIR.name == ".jax_cache"
    assert (compile_cache.DEFAULT_DIR.parent / "src" / "repro").is_dir()
    assert jax.config.jax_compilation_cache_dir == path
