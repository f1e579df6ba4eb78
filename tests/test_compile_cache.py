"""The compile-cache helper (kubernetriks_tpu/compile_cache.py): a cache
placed from outside wins and code sets nothing; otherwise the fixed
<checkout>/.jax_cache. Plus the interaction the compile-once gates depend
on: a retrace whose executable comes from the persistent cache is still
seen by the recompile sentinel."""

import os

import jax
import jax.numpy as jnp
import pytest

from kubernetriks_tpu.compile_cache import place_compile_cache
from kubernetriks_tpu.recompile import RecompileSentinel

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def restore_cache_config():
    saved = {
        name: getattr(jax.config, name)
        for name in (
            "jax_compilation_cache_dir",
            "jax_enable_compilation_cache",
            "jax_persistent_cache_min_compile_time_secs",
        )
    }
    yield
    for name, value in saved.items():
        jax.config.update(name, value)


def test_env_placement_wins(monkeypatch, restore_cache_config):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/from/outside")
    assert place_compile_cache() == "/placed/from/outside"
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_fixed_checkout_path(monkeypatch, restore_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(CHECKOUT, ".jax_cache")
    assert place_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    with open(os.path.join(CHECKOUT, ".gitignore")) as fh:
        assert ".jax_cache/" in fh.read().split()


def test_sentinel_sees_persistent_cache_hit(
    tmp_path, restore_cache_config, caplog
):
    import logging

    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compilation_cache.reset_cache()

    @jax.jit
    def cached_probe(x):
        return jnp.sin(x) @ x.T

    try:
        cached_probe(jnp.ones((8, 8))).block_until_ready()
        assert any("cached_probe" in f for f in os.listdir(tmp_path))
        jax.clear_caches()  # drop the in-memory executable, keep the disk
        with caplog.at_level(logging.DEBUG, "jax._src.compiler"):
            with RecompileSentinel("raise") as sentinel:
                cached_probe(jnp.ones((8, 8))).block_until_ready()
                assert "jit(cached_probe)" in sentinel.events
        assert "cache hit for 'jit_cached_probe'" in caplog.text
    finally:
        compilation_cache.reset_cache()
