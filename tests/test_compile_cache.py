"""The persistent compilation cache helper (launch/compile_cache.py):
where the cache lands, and that a second run of the same program
compiles nothing new."""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache as cc

from repro.launch.compile_cache import DEFAULT_CACHE_DIR, enable_compile_cache

_KEYS = ("jax_enable_compilation_cache", "jax_compilation_cache_dir",
         "jax_persistent_cache_min_compile_time_secs",
         "jax_persistent_cache_min_entry_size_bytes")


@pytest.fixture
def restore_cache_config():
    saved = {k: getattr(jax.config, k) for k in _KEYS}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    cc.reset_cache()


@pytest.fixture
def cache_events():
    seen = []

    def listener(event, **_):
        if event.startswith("/jax/compilation_cache/cache_"):
            seen.append(event.rsplit("/", 1)[-1])

    jax.monitoring.register_event_listener(listener)
    yield seen
    jax.monitoring.unregister_event_listener(listener)


def _compile_once(x):
    # a fresh function object each call, so only the persistent cache can
    # serve the second compile
    return jax.jit(lambda v: jnp.tanh(v) * 3.0 + 1.0).lower(x).compile()


def test_env_dir_is_used_and_second_run_compiles_nothing(
        tmp_path, monkeypatch, restore_cache_config, cache_events):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    default_before = DEFAULT_CACHE_DIR.exists() and sorted(
        os.listdir(DEFAULT_CACHE_DIR))
    x = jnp.ones((16, 8), jnp.float32)
    assert enable_compile_cache() == str(tmp_path)
    _compile_once(x)
    first = list(cache_events)
    assert first and set(first) == {"cache_misses"}, first
    assert os.listdir(tmp_path), "nothing written to the cache directory"
    jax.clear_caches()
    _compile_once(x)
    second = cache_events[len(first):]
    assert second and set(second) == {"cache_hits"}, second
    # nothing landed in the in-checkout default while the variable was set
    assert (DEFAULT_CACHE_DIR.exists() and sorted(
        os.listdir(DEFAULT_CACHE_DIR))) == default_before


def test_unset_env_uses_fixed_checkout_dir(monkeypatch,
                                           restore_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = enable_compile_cache()
    assert path == str(DEFAULT_CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == path
    assert DEFAULT_CACHE_DIR.name == ".jax_cache"
    assert (DEFAULT_CACHE_DIR.parent / "src" / "repro").is_dir()
