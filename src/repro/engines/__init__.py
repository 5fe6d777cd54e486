"""repro.engines — pluggable FFT engine registry (the planner's codelets).

The paper's processor reuses one butterfly array under a control unit;
the software control unit is ``repro.plan``, and this package is the pool
of engines it schedules. Every engine — the four jnp schedules, the two
fused Pallas kernels, the double-precision ``reference_x64`` backend, and
any third-party registration — is an :class:`EngineSpec` describing what
it can do (kinds × precisions × backend × VMEM needs) and how to run it.
The planner enumerates the registry by capability; adding a backend or a
precision is a registration, not a planner edit.

    from repro.engines import iter_engines, get_engine, engine, CostHints

    for spec in iter_engines(kind="fft2d", precision="single"):
        print(spec.name, spec.backend, spec.radix)

Importing this package registers the built-in engines.
"""

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
    "register_engine",
    "registered_backends",
    "registered_variants",
    "unregister_engine",
]


def apply_engine(name: str, kind: str, x, *, direction: str = "fwd",
                 axis: int | None = None):
    """Run ``x`` through engine ``name``'s executor for ``(kind, direction)``.

    This is the fallback the ``repro.core`` engine entries take for any
    variant their builtin dispatch chains do not recognise — which is how
    a registered engine (e.g. ``reference_x64``) serves every existing
    call path (``repro.xfft``, ``repro.plan.execute``, MEASURE sweeps,
    the serve layer) without those layers learning its name.

    ``x`` must be the caller's ORIGINAL array: every jnp touch (asarray,
    moveaxis, ...) happens in here, inside ``jax.enable_x64`` for engines
    that require it — outside that scope jax re-canonicalizes 64-bit
    dtypes down to 32 and a double input would be silently truncated
    before the engine ever saw it. ``axis`` (1D kinds only) names the
    transform axis; the executor itself always sees axes-last layout.

    The ``engine.apply`` dispatch span is NOT emitted here: it lives in
    :func:`repro.resilience.ladder.run_plan`, which wraps every planned
    and forced dispatch for *all* engines (builtin chains included) and
    feeds the calibration ledger observed durations. Emitting here too
    would double-count registry engines — and MEASURE sweeps, which call
    executors directly, must stay out of the observed population anyway.
    """
    spec = get_engine(name)
    fn = spec.op(kind, direction)

    def run():
        import jax.numpy as jnp

        arr = jnp.asarray(x)
        if axis is not None and kind in ("fft1d", "rfft1d"):
            ax = axis % arr.ndim
            if ax != arr.ndim - 1:
                return jnp.moveaxis(fn(jnp.moveaxis(arr, ax, -1)), -1, ax)
        return fn(arr)

    if spec.requires_x64:
        from repro._x64 import enable_x64

        with enable_x64():
            return run()
    return run()
