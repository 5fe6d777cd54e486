"""JAX's persistent compilation cache, kept in one place.

Entry points that run on the chip call :func:`enable_compile_cache` once,
before their first compile. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
already keeps its cache there and this module sets nothing. Otherwise the
cache goes to ``<checkout>/.jax_cache`` — a fixed path, because the path
is part of what a later process must find again (a temp, pid- or
time-derived directory would never hit).
"""

from __future__ import annotations

import os
import pathlib

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: ``<checkout>/.jax_cache`` (this file is ``<checkout>/src/repro/...``).
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def cache_dir() -> pathlib.Path:
    """Where the persistent cache lives: ``$JAX_COMPILATION_CACHE_DIR`` if
    set, else :data:`DEFAULT_DIR`."""
    env = os.environ.get(ENV_VAR)
    return pathlib.Path(env) if env else DEFAULT_DIR


def enable_compile_cache() -> tuple[str, bool]:
    """Turn the persistent cache on; returns ``(directory, held_entries)``
    where ``held_entries`` says whether the directory already held cached
    programs before this process compiled anything."""
    import jax

    path = cache_dir()
    held = path.is_dir() and any(path.iterdir())
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", str(path))
    return str(path), held
