"""repro.engines — pluggable FFT engine registry (the planner's codelets).

The paper's processor reuses one butterfly array under a control unit;
the software control unit is ``repro.plan``, and this package is the pool
of engines it schedules. Every engine — the four jnp schedules, the two
fused Pallas kernels, the double-precision ``reference_x64`` backend, and
any third-party registration — is an :class:`EngineSpec` describing what
it can do (kinds × precisions × backend × VMEM needs) and how to run it.
The planner enumerates the registry by capability; adding a backend or a
precision is a registration, not a planner edit. This package is also the
one place an engine name becomes code: every dispatcher runs an engine
through :func:`apply_engine` or its spec's op.

    from repro.engines import iter_engines, get_engine, engine, CostHints

    for spec in iter_engines(kind="fft2d", precision="single"):
        print(spec.name, spec.backend, spec.radix)

Importing this package registers the built-in engines.
"""

import inspect

from repro.engines.registry import (
    PRECISIONS,
    CostHints,
    EngineSpec,
    engine,
    get_engine,
    has_engine,
    iter_engines,
    register_engine,
    registered_backends,
    registered_variants,
    unregister_engine,
)

# Importing these modules registers the built-in engines as a side effect.
from repro.engines import builtin as _builtin  # noqa: F401
from repro.engines import x64 as _x64  # noqa: F401

__all__ = [
    "PRECISIONS",
    "CostHints",
    "EngineSpec",
    "apply_engine",
    "engine",
    "get_engine",
    "has_engine",
    "iter_engines",
    "op_takes",
    "register_engine",
    "registered_backends",
    "registered_variants",
    "unregister_engine",
]


def op_takes(op, name: str) -> bool:
    """True when the executor ``op`` takes the keyword ``name``: ``axis``
    (a 1D op that moves its transform axis inside its own program) or a
    plan field (``unroll``, ``chunks``)."""
    return name in inspect.signature(op).parameters


def apply_engine(name: str, kind: str, x, *, direction: str = "fwd",
                 axis: int | None = None):
    """Run ``x`` through engine ``name``'s executor for ``(kind, direction)``.

    The one call form of dispatch: the front door, ``repro.plan.execute``
    and MEASURE sweeps all run an engine through here (or through the
    spec's op directly where they pass a plan field such as ``chunks``).

    ``x`` is the caller's ORIGINAL array: every jnp touch happens in here,
    inside ``jax.enable_x64`` for an engine that requires it — outside that
    scope jax re-canonicalizes 64-bit dtypes down to 32 and a double input
    would be truncated before the engine saw it. ``axis`` (1D kinds only)
    names the transform axis: an op that takes ``axis`` gets it; any other
    op sees the axis moved last.

    The ``engine.apply`` dispatch span is NOT emitted here: it lives in
    :func:`repro.resilience.ladder.run_plan`, which wraps every planned and
    forced dispatch and feeds the calibration ledger observed durations;
    MEASURE sweeps, which also call through here, stay out of that
    population.
    """
    spec = get_engine(name)
    fn = spec.op(kind, direction)

    def run():
        import jax.numpy as jnp

        arr = jnp.asarray(x)
        if axis is None or kind not in ("fft1d", "rfft1d"):
            return fn(arr)
        ax = axis % arr.ndim
        if op_takes(fn, "axis"):
            return fn(arr, axis=ax)
        if ax == arr.ndim - 1:
            return fn(arr)
        return jnp.moveaxis(fn(jnp.moveaxis(arr, ax, -1)), -1, ax)

    if spec.requires_x64:
        from repro._x64 import enable_x64

        with enable_x64():
            return run()
    return run()
