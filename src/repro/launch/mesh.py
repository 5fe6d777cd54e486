"""Production mesh builders (assignment-mandated shapes).

A FUNCTION, not a module constant — importing this module must never touch
jax device state (the dry-run sets XLA_FLAGS before first jax init)."""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(axis_shapes, axis_names):
    """``jax.make_mesh`` with Auto axes (jax defaults to Explicit), so
    sharding constraints and ``jax.shard_map`` partial-manual axes behave
    the way the models and the pencil FFT were written for."""
    names = tuple(axis_names)
    return jax.make_mesh(tuple(axis_shapes), names, axis_types=(AxisType.Auto,) * len(names))


def make_production_mesh(*, multi_pod: bool = False):
    """(16,16) data×model single pod; (2,16,16) pod×data×model for 2 pods."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(*, multi_pod: bool = False):
    """Tiny analogue for CI subprocesses (8 fake devices)."""
    shape = (2, 2, 2) if multi_pod else (4, 2)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
