"""Compiling on record: the ``compile.*`` counters ``repro.compile_cache``
feeds from ``jax.monitoring``."""

import jax
import jax.numpy as jnp
import pytest

from repro import compile_cache, obs

_CONFIG = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
           "jax_persistent_cache_min_compile_time_secs")


@pytest.fixture
def cache_in_tmp(monkeypatch, tmp_path):
    """The persistent cache in ``tmp_path``, every program written to it."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    monkeypatch.setattr(compile_cache, "cache_dir", lambda: tmp_path)
    before = {name: getattr(jax.config, name) for name in _CONFIG}
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compilation_cache.reset_cache()
    yield tmp_path
    for name, value in before.items():
        jax.config.update(name, value)
    compilation_cache.reset_cache()


def _delta(before, after, name):
    return after.get(name, 0) - before.get(name, 0)


def test_fresh_jit_counts_one_miss_then_one_hit(cache_in_tmp):
    compile_cache.enable_compile_cache()
    compile_cache.enable_compile_cache()        # listeners stay registered once
    assert jax.config.jax_compilation_cache_dir == str(cache_in_tmp)
    x = jnp.arange(8.0)

    def f(v):
        return jnp.cos(v) * 7.25 - 2.0

    before = obs.counters()
    jax.jit(f)(x).block_until_ready()
    cold = obs.counters()
    assert _delta(before, cold, "compile.cache.miss") == 1
    assert _delta(before, cold, "compile.cache.hit") == 0
    for phase in ("compile.tracing_us", "compile.lowering_us", "compile.backend_us"):
        assert _delta(before, cold, phase) > 0
    assert any(cache_in_tmp.iterdir())

    jax.clear_caches()                          # forget the in-memory program
    jax.jit(f)(x).block_until_ready()
    warm = obs.counters()
    assert _delta(cold, warm, "compile.cache.hit") == 1
    assert _delta(cold, warm, "compile.cache.miss") == 0
    assert _delta(cold, warm, "compile.backend_us") > 0   # the load from the cache


def test_nested_phase_counts_once():
    """An inner trace (a ``jit`` traced inside another) lies within the outer
    one's time: only the outermost duration is added."""
    event = "/jax/core/compile/jaxpr_trace_duration"
    before = obs.counters().get("compile.tracing_us", 0)
    compile_cache._on_phase_start(event, 0.0)
    compile_cache._on_phase_start(event, 0.0)
    compile_cache._on_phase_end(event, 0.25)
    assert obs.counters().get("compile.tracing_us", 0) == before
    compile_cache._on_phase_end(event, 1.0)
    assert obs.counters()["compile.tracing_us"] == before + 1_000_000
    compile_cache._on_phase_end("/jax/some/other_duration", 5.0)
    compile_cache._on_phase_start("/jax/some/other_duration", 0.0)
    assert obs.counters()["compile.tracing_us"] == before + 1_000_000


def test_cache_events_feed_their_counters():
    before = obs.counters()
    compile_cache._on_event("/jax/compilation_cache/cache_hits")
    compile_cache._on_event("/jax/compilation_cache/cache_misses")
    compile_cache._on_event("/jax/compilation_cache/compile_requests_use_cache")
    after = obs.counters()
    assert _delta(before, after, "compile.cache.hit") == 1
    assert _delta(before, after, "compile.cache.miss") == 1
