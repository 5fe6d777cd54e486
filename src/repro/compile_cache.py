"""JAX's persistent compilation cache, kept in one place.

Entry points that run on the chip call :func:`enable_compile_cache` once,
before their first compile. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
already keeps its cache there and this module sets nothing. Otherwise the
cache goes to ``<checkout>/.jax_cache`` — a fixed path, because the path
is part of what a later process must find again (a temp, pid- or
time-derived directory would never hit).

It also puts compiling on record: :func:`enable_compile_cache` registers
``jax.monitoring`` listeners (once per process) that feed ``repro.obs``
counters. ``compile.cache.hit`` and ``compile.cache.miss`` count
persistent-cache hits and writes; ``compile.tracing_us``,
``compile.lowering_us`` and ``compile.backend_us`` sum the microseconds
spent tracing to a jaxpr, lowering to a module, and compiling it or
loading it from the cache. A trace nested inside another (an inner
``jit`` traced as part of the outer one) counts once, in the outer.
"""

from __future__ import annotations

import os
import pathlib
import threading

from repro import obs

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: ``jax.monitoring`` events -> the ``repro.obs`` counters they feed.
CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "compile.cache.hit",
    "/jax/compilation_cache/cache_misses": "compile.cache.miss",
}
PHASE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "compile.tracing_us",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile.lowering_us",
    "/jax/core/compile/backend_compile_duration": "compile.backend_us",
}

#: ``<checkout>/.jax_cache`` (this file is ``<checkout>/src/repro/...``).
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def cache_dir() -> pathlib.Path:
    """Where the persistent cache lives: ``$JAX_COMPILATION_CACHE_DIR`` if
    set, else :data:`DEFAULT_DIR`."""
    env = os.environ.get(ENV_VAR)
    return pathlib.Path(env) if env else DEFAULT_DIR


_open_phases = threading.local()  # phase event -> depth, per thread
_listening = False
_listening_lock = threading.Lock()


def _on_event(event: str, **_) -> None:
    name = CACHE_EVENTS.get(event)
    if name is not None:
        obs.count(name)


def _on_phase_start(event: str, _value, **_) -> None:
    # JAX records each phase's start as a scalar and its duration at the
    # end; the depth between the two keeps nested traces from counting twice.
    if event in PHASE_EVENTS:
        depth = getattr(_open_phases, "depth", None)
        if depth is None:
            depth = _open_phases.depth = {}
        depth[event] = depth.get(event, 0) + 1


def _on_phase_end(event: str, seconds: float, **_) -> None:
    name = PHASE_EVENTS.get(event)
    if name is None:
        return
    depth = getattr(_open_phases, "depth", None) or {}
    left = max(depth.get(event, 0) - 1, 0)
    depth[event] = left
    if left == 0:
        obs.count(name, int(seconds * 1e6))


def count_compiles() -> None:
    """Register the listeners behind the ``compile.*`` counters; a second
    call registers nothing."""
    global _listening
    from jax import monitoring

    with _listening_lock:
        if _listening:
            return
        monitoring.register_event_listener(_on_event)
        monitoring.register_scalar_listener(_on_phase_start)
        monitoring.register_event_duration_secs_listener(_on_phase_end)
        _listening = True


def enable_compile_cache() -> tuple[str, bool]:
    """Turn the persistent cache on and count compiles
    (:func:`count_compiles`); returns ``(directory, held_entries)`` where
    ``held_entries`` says whether the directory already held cached
    programs before this process compiled anything."""
    import jax

    count_compiles()
    path = cache_dir()
    held = path.is_dir() and any(path.iterdir())
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", str(path))
    return str(path), held
