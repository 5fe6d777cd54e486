"""Plan objects: the software rendition of the paper's control unit.

The paper's 2D processor owes its area savings to a *control unit* that
schedules a small pool of butterfly units across stages, and a *RAM
controller* that sequences the two 1D engines through the ping-pong
buffers. In software the analogous decisions — which 1D schedule
(``looped`` / ``unrolled`` / ``stockham``), how far to unroll the
streaming scan, how many slabs to chunk the pencil corner-turn into —
are made *per problem*, keyed by backend, device kind, shape, dtype and
device count. An :class:`FFTPlan` freezes one such decision set; the
autotuner (``repro.plan.autotune``) produces plans and the cache
(``repro.plan.cache``) remembers them across calls and processes.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

#: Bumped whenever plan semantics change; embedded in every cache key so a
#: stale on-disk cache can never hand an old-format plan to new code.
#: v2: radix-4 + fused kernel variants, real-input (rfft) problem kinds and
#: the transform-direction key field — v1 wisdom tuned without these
#: candidates is stale by construction, so bumping forces a re-tune.
#: v3: norm and axes join the key (the ``repro.xfft`` front door plans whole
#: calls, scaling convention and transform axes included, through
#: ``resolve_call``) — v2 wisdom carries neither field, so it is orphaned.
#: v4: norm LEAVES the key again — the scaling convention is applied outside
#: the engine (``repro.xfft._scale``), so backward/ortho/forward share one
#: tuned entry and a service tuned under one convention serves all three.
#: v4 also adds the ``oaconv2d`` problem kind (overlap-save tiled 2D
#: convolution) and the plan ``tile`` field it resolves; v3 wisdom keyed
#: norm-per-entry is orphaned by the version prefix.
#: v5: engines became a registry (``repro.engines``) and the key gained the
#: capability constraints resolution runs under — the numeric ``precision``
#: ("single"/"double") and the scoped engine-``backends`` restriction — so
#: wisdom tuned for one engine population can never be served to an
#: incompatible one; v4 wisdom carries neither field and is orphaned.
PLAN_SCHEMA_VERSION = 5

#: Problem kinds the planner understands (r* = real-input two-for-one;
#: oaconv2d = overlap-save tiled 2D convolution, whose shape convention is
#: (H, W, KH, KW) — image dims then kernel dims — and whose plan carries
#: the FFT tile in ``FFTPlan.tile``).
KINDS = (
    "fft1d", "fft2d", "fft2d_stream", "fft2d_pencil", "rfft1d", "rfft2d",
    "oaconv2d",
)

#: Numeric precisions a ProblemKey may carry ("single" = the paper's
#: complex64 datapath, "double" = complex128 via an x64-capable engine).
#: ONE source of truth: ``repro.engines.registry.PRECISIONS`` — re-exported
#: here lazily (module ``__getattr__`` below) so key validation and engine
#: registration can never disagree on the domain.

#: Transform directions a ProblemKey may carry. Inverse transforms tune
#: separately: their conjugation wrapper and 1/N scaling shift the optimum.
DIRECTIONS = ("fwd", "inv")

#: Normalization conventions (scipy.fft names): where the 1/N lives. The
#: convention is NOT part of the plan key: every entry point applies the
#: norm as a scale outside the engine, so the schedule optimum cannot
#: depend on it and all three conventions share one tuned entry.
NORMS = ("backward", "ortho", "forward")

#: Single-precision dtype labels and their double-precision widenings —
#: ``ProblemKey.__post_init__`` maps a key's dtype through this whenever
#: ``precision == "double"``.
_WIDE_DTYPES = {"complex64": "complex128", "float32": "float64"}

#: Input layouts of a sharded (``fft2d_pencil``) problem: which of the two
#: transform axes the devices split. Single-device kinds carry none.
LAYOUTS = ("rows", "cols")

#: Canonical transform axes per kind — the axes every entry point moves the
#: transform onto before keying (1D kinds transform the last axis, 2D kinds
#: the trailing two). A ProblemKey built without explicit axes gets these,
#: so pre-xfft call sites and the xfft front door share cache entries.
_CANONICAL_AXES = {
    "fft1d": (-1,),
    "rfft1d": (-1,),
    "fft2d": (-2, -1),
    "rfft2d": (-2, -1),
    "fft2d_stream": (-2, -1),
    "fft2d_pencil": (-2, -1),
    "oaconv2d": (-2, -1),
}


@dataclasses.dataclass(frozen=True)
class ProblemKey:
    """Identity of one FFT problem: what the control unit dispatches on.

    ``shape`` is the concrete array shape seen by the entry point (for
    ``fft1d`` the transform axis is last; for 2D kinds the trailing two
    axes are H, W; for ``fft2d_stream`` the leading axis is time; for
    ``fft2d_pencil`` the global shape). ``layout`` is the sharded input's
    layout (:data:`LAYOUTS`, ``"rows"`` by default) on ``fft2d_pencil``
    keys, and empty on every other kind.
    """

    kind: str                  # one of KINDS
    backend: str               # jax.default_backend(): "cpu" | "gpu" | "tpu"
    device_kind: str           # jax device_kind, e.g. "TPU v5 lite", "cpu"
    shape: Tuple[int, ...]
    dtype: str                 # canonical dtype name, e.g. "complex64"
    n_devices: int = 1
    direction: str = "fwd"     # "fwd" | "inv" — inverse transforms tune apart
    axes: Tuple[int, ...] = () # transform axes; () -> canonical for the kind
    precision: str = "single"  # "single" | "double" — engine-capability filter
    backends: Tuple[str, ...] = ()  # engine-backend scope; () = unrestricted
    layout: str = ""           # pencil input layout: "rows" | "cols"

    def __post_init__(self):
        if self.kind not in KINDS:
            raise ValueError(f"unknown problem kind {self.kind!r}; want one of {KINDS}")
        if self.direction not in DIRECTIONS:
            raise ValueError(
                f"unknown direction {self.direction!r}; want one of {DIRECTIONS}"
            )
        from repro.engines.registry import PRECISIONS  # lazy: one domain

        if self.precision not in PRECISIONS:
            raise ValueError(
                f"unknown precision {self.precision!r}; want one of {PRECISIONS}"
            )
        if self.precision == "double":
            # Normalize the dtype label to the width a double-precision
            # engine actually moves. Done HERE — the one place every key is
            # born (resolve_call, plan_fft, direct construction) — so double
            # wisdom can never split across callers that spelled the dtype
            # at different widths.
            object.__setattr__(
                self, "dtype", _WIDE_DTYPES.get(str(self.dtype), str(self.dtype))
            )
        object.__setattr__(self, "shape", tuple(int(s) for s in self.shape))
        axes = tuple(int(a) for a in self.axes) or _CANONICAL_AXES[self.kind]
        object.__setattr__(self, "axes", axes)
        # Canonicalize the engine-backend scope (sorted, deduplicated) so
        # config(backend=("pallas", "jnp")) and ("jnp", "pallas") share keys.
        object.__setattr__(self, "backends", tuple(sorted(set(self.backends))))
        if self.kind == "fft2d_pencil":
            object.__setattr__(self, "layout", self.layout or "rows")
            if self.layout not in LAYOUTS:
                raise ValueError(
                    f"unknown pencil layout {self.layout!r}; want one of {LAYOUTS}"
                )
        elif self.layout:
            raise ValueError(f"kind {self.kind!r} takes no layout, got {self.layout!r}")

    def cache_key(self) -> str:
        """Stable, versioned string key for the plan cache.

        The engine-capability constraints — precision and any scoped
        backend restriction — are part of the key: a plan tuned for one
        engine population (say complex64 jnp+pallas) is never wisdom for
        an incompatible one (complex128 x64, or a pallas-only scope).
        """
        shape = "x".join(str(s) for s in self.shape)
        axes = ",".join(str(a) for a in self.axes)
        engines = ",".join(self.backends) if self.backends else "*"
        layout = f"|{self.layout}" if self.layout else ""
        return (
            f"v{PLAN_SCHEMA_VERSION}|{self.kind}|{self.direction}|{self.backend}"
            f"|{self.device_kind}|{shape}|{self.dtype}|d{self.n_devices}"
            f"|ax{axes}|{self.precision}|be{engines}{layout}"
        )

    def to_dict(self) -> dict:
        out = {
            "kind": self.kind,
            "backend": self.backend,
            "device_kind": self.device_kind,
            "shape": list(self.shape),
            "dtype": self.dtype,
            "n_devices": self.n_devices,
            "direction": self.direction,
            "axes": list(self.axes),
            "precision": self.precision,
            "backends": list(self.backends),
        }
        if self.layout:
            out["layout"] = self.layout
        return out

    @classmethod
    def from_dict(cls, d: dict) -> "ProblemKey":
        return cls(
            kind=d["kind"],
            backend=d["backend"],
            device_kind=d["device_kind"],
            shape=tuple(d["shape"]),
            dtype=d["dtype"],
            n_devices=int(d["n_devices"]),
            direction=d.get("direction", "fwd"),
            axes=tuple(d.get("axes", ())),
            precision=d.get("precision", "single"),
            backends=tuple(d.get("backends", ())),
            layout=d.get("layout", ""),
        )


@dataclasses.dataclass(frozen=True)
class FFTPlan:
    """One frozen scheduling decision for a :class:`ProblemKey`.

    Fields beyond ``variant`` exist so later PRs (sharding, batching,
    multi-backend) plug into the same decision point instead of growing
    new keyword arguments on every entry point:

      axis_order  — pass order for separable 2D transforms; ``(-1, -2)``
                    is rows-then-columns (paper fig. 1).
      precision   — numeric precision the plan resolves under ("single"
                    = the paper's complex64 datapath, "double" = the x64
                    engine family); mirrors ``key.precision``.
      unroll      — ``lax.scan`` unroll for the streaming pipeline.
      chunks      — corner-turn slab count for the overlapped pencil path.
      tile        — (TH, TW) FFT tile for ``oaconv2d`` plans: the largest
                    tile whose fused-kernel working set stays inside VMEM
                    with the best compute-per-output ratio; ``None`` for
                    every other kind.
      degrade_reason — why a MEASURE request produced this ESTIMATE plan
                    (``"estimate_only_kind"`` for pencil/oaconv problems,
                    ``"trace_not_clean"`` when resolution happened inside
                    a jit trace, ``"forced_variant"`` under a scoped
                    variant pin); ``None`` when nothing degraded. Persists
                    into wisdom files, so a shipped cache says *why* an
                    entry never tuned.
    """

    key: ProblemKey
    variant: str                       # name of a registered engine
    axis_order: Tuple[int, ...] = (-1, -2)
    precision: str = "single"
    unroll: int = 1
    chunks: int = 1
    mode: str = "estimate"             # "estimate" | "measure"
    est_time_s: float = 0.0            # roofline-model time (ESTIMATE)
    measured_us: Optional[float] = None  # winning candidate time (MEASURE)
    tile: Optional[Tuple[int, int]] = None  # oaconv2d FFT tile (TH, TW)
    degrade_reason: Optional[str] = None  # why measure degraded to estimate

    def __post_init__(self):
        from repro.engines import has_engine, registered_variants  # lazy

        if not has_engine(self.variant):
            # Name what IS registered, live — never a stale hardcoded tuple.
            raise ValueError(
                f"plan variant must be a concrete registered engine, got "
                f"{self.variant!r} (registered engines: {registered_variants()})"
            )
        # precision is DERIVED state: always the key's, so no construction
        # site can ever produce a double-keyed plan labeled "single".
        object.__setattr__(self, "precision", self.key.precision)
        if self.unroll < 1 or self.chunks < 1:
            raise ValueError("unroll and chunks must be >= 1")

    def to_dict(self) -> dict:
        return {
            "key": self.key.to_dict(),
            "variant": self.variant,
            "axis_order": list(self.axis_order),
            "precision": self.precision,
            "unroll": self.unroll,
            "chunks": self.chunks,
            "mode": self.mode,
            "est_time_s": self.est_time_s,
            "measured_us": self.measured_us,
            "tile": None if self.tile is None else list(self.tile),
            "degrade_reason": self.degrade_reason,
        }

    @classmethod
    def from_dict(cls, d: dict) -> "FFTPlan":
        tile = d.get("tile")
        return cls(
            key=ProblemKey.from_dict(d["key"]),
            variant=d["variant"],
            axis_order=tuple(d["axis_order"]),
            precision=d["precision"],
            unroll=int(d["unroll"]),
            chunks=int(d["chunks"]),
            mode=d["mode"],
            est_time_s=float(d["est_time_s"]),
            measured_us=None if d.get("measured_us") is None else float(d["measured_us"]),
            tile=None if tile is None else (int(tile[0]), int(tile[1])),
            degrade_reason=d.get("degrade_reason"),
        )


def problem_key(
    kind: str,
    shape: Tuple[int, ...],
    dtype: str = "complex64",
    n_devices: int = 1,
    direction: str = "fwd",
    axes: Optional[Tuple[int, ...]] = None,
    precision: str = "single",
    backends: Tuple[str, ...] = (),
    layout: str = "",
) -> ProblemKey:
    """Build a :class:`ProblemKey` for the *current* JAX backend/device.

    ``axes=None`` keys on the kind's canonical axes (transform axes moved
    last), which is what every entry point does before dispatching. The
    ``norm`` convention is deliberately absent: it is a post-engine scale,
    so all three conventions resolve to the same key (schema v4).
    ``precision`` and ``backends`` are the engine-capability constraints
    resolution runs under (schema v5); both come from the scoped
    ``repro.xfft.config`` when resolution goes through ``resolve_call``.
    ``layout`` is a pencil key's input layout (``"rows"`` when empty).
    """
    import jax

    devices = jax.devices()
    return ProblemKey(
        kind=kind,
        backend=jax.default_backend(),
        device_kind=devices[0].device_kind if devices else "unknown",
        shape=tuple(shape),
        dtype=str(dtype),
        n_devices=int(n_devices),
        direction=direction,
        axes=tuple(axes) if axes else (),
        precision=precision,
        backends=tuple(backends),
        layout=layout,
    )


def __getattr__(name: str):
    # Deprecation alias: the hardcoded engine tuple became the registry
    # (``repro.engines``). Derived live so third-party registrations show
    # up; restricted to single precision so pre-registry callers see
    # exactly the engine population the old tuple named.
    if name == "PLAN_VARIANTS":
        from repro.engines import registered_variants

        return registered_variants(precision="single")
    # Lazy re-export: the precision domain lives on the engine registry
    # (the leaf module) so registration and key validation share it.
    if name == "PRECISIONS":
        from repro.engines.registry import PRECISIONS

        return PRECISIONS
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
