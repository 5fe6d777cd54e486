"""The engine registry: capability-described FFT engines, FFTW-style.

FFTW3 owes its longevity to the planner/codelet split: codelets declare
what they can do, the planner enumerates whatever is registered, and new
codelets are registrations rather than planner edits. This module is that
split for the repo. An :class:`EngineSpec` is the codelet descriptor — a
name (the ``plan.variant`` value), an execution *backend* family, the
problem kinds and precisions it can serve, its radix/fusion geometry, a
VMEM working-set callback the planner sizes against, and the cost-model
hints ESTIMATE ranks with. ``repro.plan`` enumerates the registry by
capability (kind × precision × backend × device count × VMEM fit)
instead of a hardcoded variant tuple, so a new backend, precision or
kernel lands as::

    from repro.engines import CostHints, engine

    @engine("my_split_radix", backend="jnp", kinds=("fft1d", "fft2d"),
            cost=CostHints(traffic_factor=4.0, flop_scale=0.8))
    def my_ops(kind, direction):
        ...  # return the transform callable for (kind, direction)

and is immediately a planner candidate, a MEASURE sweep entrant, a
``benchmarks/fft_bench.py`` row and a ``tests/engines`` conformance case.

This module imports nothing from the rest of the repo at module scope:
``repro.plan`` and ``repro.xfft`` build on it.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Tuple

__all__ = [
    "PRECISIONS",
    "CostHints",
    "EngineSpec",
    "engine",
    "get_engine",
    "has_engine",
    "iter_engines",
    "register_engine",
    "registered_backends",
    "registered_variants",
    "unregister_engine",
]

#: Numeric precisions an engine may declare: "single" is the paper's
#: complex64/float32 datapath, "double" is complex128/float64.
PRECISIONS = ("single", "double")


@dataclasses.dataclass(frozen=True)
class CostHints:
    """ESTIMATE-model coefficients for one engine (see ``plan.autotune``).

    traffic_factor   — HBM element-touches per butterfly pass (gather-heavy
                       schedules pay ~6, contiguous Stockham-style ~4).
    stage_overhead_s — per-stage dispatch overhead (seconds).
    flop_scale       — multiplier on the radix-2 butterfly FLOP count
                       (radix-4 merges twiddles: ~0.85).
    entry_overhead_s — fixed per-call cost (e.g. entering a ``fori_loop``
                       with carried state).
    """

    traffic_factor: float = 4.0
    stage_overhead_s: float = 0.8e-6
    flop_scale: float = 1.0
    entry_overhead_s: float = 0.0


@dataclasses.dataclass(frozen=True)
class EngineSpec:
    """One registered FFT engine: identity, capabilities, cost, executors.

    name               — registry key; the value ``FFTPlan.variant`` holds.
    backend            — execution-backend family ("jnp" = plain XLA ops,
                         "pallas" = the fused TPU kernels, "x64" = the
                         double-precision reference). ``xfft.config(
                         backend=...)`` scopes planning to a subset.
    kinds              — ``repro.plan`` problem kinds the engine serves.
    precisions         — subset of :data:`PRECISIONS`.
    dtypes             — canonical I/O dtype names, documentation-grade.
    radix              — butterfly radix (stage count = log_radix N).
    fused              — True for whole-transform-in-VMEM Pallas kernels.
    reliable           — True marks an always-works degradation rung (plain
                         XLA ops, no lowering cliffs): when the resilience
                         quarantine would exclude every candidate for a
                         problem, reliable engines come back regardless so
                         the ladder always has a bottom.
    single_device_only — engine cannot take part in multi-device plans.
    requires_x64       — engine computes under ``jax.enable_x64``.
    working_set        — optional callback ``(ProblemKey) -> bytes|None``:
                         the smallest VMEM residency the engine needs for
                         that problem; the planner drops the engine when it
                         exceeds ``repro.kernels.ops.vmem_budget_bytes()``.
    predicate          — optional extra capability check ``(ProblemKey) ->
                         bool`` for constraints the generic fields cannot
                         express (e.g. power-of-two transform dims).
    cost               — :class:`CostHints` for the analytic ESTIMATE mode.
    ops                — op factory ``(kind, direction) -> callable|None``;
                         the callable takes one array in the kind's
                         canonical layout (transform axes last) and returns
                         the transform under the engine's native backward
                         convention.
    """

    name: str
    backend: str
    kinds: Tuple[str, ...]
    precisions: Tuple[str, ...] = ("single",)
    dtypes: Tuple[str, ...] = ("complex64", "float32")
    radix: int = 2
    fused: bool = False
    reliable: bool = False
    single_device_only: bool = False
    requires_x64: bool = False
    working_set: Optional[Callable] = None
    predicate: Optional[Callable] = None
    cost: CostHints = dataclasses.field(default_factory=CostHints)
    ops: Optional[Callable] = None

    def supports(self, key) -> bool:
        """True when this engine may serve ``key`` (the planner's filter:
        kind × precision × backend scope × device count × VMEM fit)."""
        if key.kind not in self.kinds:
            return False
        if getattr(key, "precision", "single") not in self.precisions:
            return False
        backends = getattr(key, "backends", ())
        if backends and self.backend not in backends:
            return False
        if self.single_device_only and key.n_devices != 1:
            return False
        if self.predicate is not None and not self.predicate(key):
            return False
        if self.working_set is not None:
            ws = self.working_set(key)
            if ws is not None:
                from repro.kernels.ops import vmem_budget_bytes  # lazy

                if ws > vmem_budget_bytes():
                    return False
        return True

    def op(self, kind: str, direction: str = "fwd") -> Callable:
        """The executor for ``(kind, direction)``; raises when unserved."""
        fn = None
        if kind in self.kinds and self.ops is not None:
            fn = self.ops(kind, direction)
        if fn is None:
            raise ValueError(
                f"engine {self.name!r} has no executor for kind {kind!r} "
                f"direction {direction!r} (declared kinds: {self.kinds})"
            )
        return fn


_REGISTRY: Dict[str, EngineSpec] = {}

#: The six seed engines. Persisted wisdom names them, the ladder's bottom
#: rung is one of them, and the fused kernels' failovers run the stockham
#: and radix4 pass programs directly: a replacement would change what
#: those plans mean, so registration refuses instead.
_PROTECTED: set = set()


def register_engine(
    spec: EngineSpec, *, replace: bool = False, _protect: bool = False
) -> EngineSpec:
    """Add ``spec`` to the registry (the non-decorator spelling).

    Validates the declaration eagerly — a typo'd kind or precision should
    fail at registration, not at the first planning call — and rejects
    duplicate names unless ``replace=True``. The six seed engines cannot
    be replaced at all (see ``_PROTECTED``) — register under a new name
    instead.
    """
    if not spec.name or not isinstance(spec.name, str):
        raise ValueError(f"engine name must be a non-empty string, got {spec.name!r}")
    if not spec.kinds:
        raise ValueError(f"engine {spec.name!r} declares no problem kinds")
    from repro.plan.plan import KINDS  # lazy: plan builds on this module

    for kind in spec.kinds:
        if kind not in KINDS:
            raise ValueError(
                f"engine {spec.name!r} declares unknown kind {kind!r}; "
                f"want members of {KINDS}"
            )
    for precision in spec.precisions:
        if precision not in PRECISIONS:
            raise ValueError(
                f"engine {spec.name!r} declares unknown precision "
                f"{precision!r}; want members of {PRECISIONS}"
            )
    if spec.name in _REGISTRY:
        if spec.name in _PROTECTED:
            raise ValueError(
                f"engine {spec.name!r} is a builtin engine and cannot be "
                "replaced; register your engine under a new name"
            )
        if not replace:
            raise ValueError(
                f"engine {spec.name!r} is already registered "
                "(pass replace=True to override)"
            )
    _REGISTRY[spec.name] = spec
    if _protect:
        _PROTECTED.add(spec.name)
    return spec


def unregister_engine(name: str) -> None:
    """Remove an engine (plugin teardown / tests); unknown names are a
    no-op. Builtin engines cannot be removed (see ``_PROTECTED``)."""
    if name in _PROTECTED:
        raise ValueError(f"builtin engine {name!r} cannot be unregistered")
    _REGISTRY.pop(name, None)


def engine(name: str, **fields):
    """Decorator-based registration: decorate the op factory.

    The decorated function is the spec's ``ops`` field — it receives
    ``(kind, direction)`` and returns the transform callable (or ``None``
    for combinations it cannot serve). Returns the registered
    :class:`EngineSpec`.
    """

    def deco(ops_factory: Callable) -> EngineSpec:
        return register_engine(EngineSpec(name=name, ops=ops_factory, **fields))

    return deco


def get_engine(name: str) -> EngineSpec:
    """Look an engine up by name; the error names what IS registered."""
    spec = _REGISTRY.get(name)
    if spec is None:
        raise ValueError(
            f"unknown engine {name!r}; registered engines: {tuple(_REGISTRY)}"
        )
    return spec


def has_engine(name: str) -> bool:
    return name in _REGISTRY


def iter_engines(
    kind: Optional[str] = None,
    precision: Optional[str] = None,
    backend: Optional[str] = None,
) -> Tuple[EngineSpec, ...]:
    """Registered engines in registration order, optionally filtered."""
    out = []
    for spec in _REGISTRY.values():
        if kind is not None and kind not in spec.kinds:
            continue
        if precision is not None and precision not in spec.precisions:
            continue
        if backend is not None and spec.backend != backend:
            continue
        out.append(spec)
    return tuple(out)


def registered_variants(precision: Optional[str] = None) -> Tuple[str, ...]:
    """Engine names, optionally restricted to one precision."""
    return tuple(s.name for s in iter_engines(precision=precision))


def registered_backends() -> Tuple[str, ...]:
    """Distinct backend families currently registered (sorted)."""
    return tuple(sorted({s.backend for s in _REGISTRY.values()}))
