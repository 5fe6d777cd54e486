"""FFTW-style planning modes: analytic ESTIMATE and timed MEASURE.

Candidates come from the ``repro.engines`` registry (capability-filtered
per problem key); ESTIMATE builds a roofline model per candidate from the
paper's analytic resource counts (``butterfly_counts``: (N/2)·log2 N
butterfly passes) plus each engine's registered cost hints
(``repro.engines.CostHints``: memory-traffic factor, per-stage dispatch
overhead, FLOP scale, fixed entry cost), which differentiate the
schedules where the roofline terms tie:

  * ``looped``   — fori_loop stages run strictly sequentially and each
                   stage is a gather/concat/gather round-trip.
  * ``unrolled`` — same traffic, but XLA sees all stages at once and can
                   fuse across them; lowest per-stage overhead.
  * ``stockham`` — autosort: no bit-reversal gather and contiguous
                   reshapes only, so ~2/3 of the per-stage traffic.
  * ``radix4``   — Stockham with 4-point butterflies: half the stage
                   passes (≈ half the traffic) and ~15% fewer FLOPs
                   (3 twiddle multiplies produce 4 outputs).
  * ``fused``/``fused_r4`` — the Pallas whole-transform kernels: ONE HBM
                   round trip on TPU. On other backends they execute in
                   interpret mode (plain XLA ops), so they are modeled
                   like their in-VMEM schedule plus launch overhead —
                   which keeps ESTIMATE honest on CPU while letting the
                   fused path dominate where it really does.

Real-input kinds (``rfft1d``/``rfft2d``) halve both the butterfly count
and the traffic: the two-for-one Hermitian pack runs ONE half-size
complex FFT and touches half the bytes.

The crossover this produces — ``unrolled`` for overhead-dominated small
transforms, the bandwidth-lean Stockham family once traffic dominates —
matches what MEASURE finds on CPU and TPU for this repo's engines.

MEASURE jits every candidate, times it (median of several runs, first
call discarded so compile time never pollutes the comparison) and keeps
the argmin, exactly like FFTW's planner running real candidates.
"""

from __future__ import annotations

import math
import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from repro.core.fft1d import butterfly_counts
from repro.core.spectral import _next_pow2
from repro.launch.roofline import Roofline
from repro.plan.plan import FFTPlan, ProblemKey
from repro.resilience import faults as _faults
from repro.resilience.breaker import quarantine

__all__ = [
    "estimate_plan",
    "measure_plan",
    "chunk_candidates",
    "oaconv_tile_candidates",
    "variant_candidates",
]

# Real FLOPs per butterfly pass: one complex multiply (6) + two complex
# add/sub (4) — the multiplier + 2 adders of the paper's butterfly unit.
_FLOPS_PER_BUTTERFLY = 10.0

# Fixed cost of a Pallas kernel launch; in interpret mode (non-TPU) the
# kernel body is traced into XLA, costing grid bookkeeping on top.
_KERNEL_LAUNCH_S = 2.0e-6
_INTERPRET_OVERHEAD_S = 20.0e-6

# CPU backends sit far off any chip's roofline; only the *ranking* matters
# for planning there, but scaling keeps est_time_s roughly honest.
_BACKEND_SLOWDOWN = {"cpu": 40.0}

#: The peak-table entry non-TPU backends rank against (then scaled by
#: ``_BACKEND_SLOWDOWN``); TPU keys use their own ``device_kind``'s entry.
_RANKING_DEVICE_KIND = "TPU v5 lite"


def _roofline_peaks(key: ProblemKey):
    """Peaks ESTIMATE prices ``key`` against: the chip's own entry in
    :data:`repro.launch.roofline.PEAKS` on TPU (an unknown kind raises),
    a ranking-only scale on every other backend."""
    from repro.launch.roofline import chip_peaks

    if key.backend == "tpu":
        return chip_peaks(key.device_kind)
    return chip_peaks(_RANKING_DEVICE_KIND)

#: Real-input (two-for-one) kinds.
_REAL_KINDS = ("rfft1d", "rfft2d")

#: Per-candidate wall-clock budget (seconds) for a MEASURE sweep. A
#: candidate whose warmup+timing loop exceeds it is skipped and recorded
#: in the ``plan.measure`` span; a sweep where EVERY candidate blows the
#: budget degrades to ESTIMATE with reason ``measure_timeout``. The check
#: runs between calls (a single hung jit cannot be preempted from Python),
#: so the guard bounds sweeps that are slow, not ones that never return.
MEASURE_CANDIDATE_BUDGET_S = 30.0


def variant_candidates(key: ProblemKey) -> Tuple[str, ...]:
    """Engines the planner may legally consider for ``key``.

    An enumeration of the ``repro.engines`` registry filtered by
    capability: problem kind × precision × scoped backend restriction ×
    device count × VMEM working-set fit (each engine's own
    ``EngineSpec.supports``). Per-engine cost tables, fused-kind lists and
    pow2/VMEM gates all live on the specs now — registering an engine is
    enough to enter every sweep.

    Engines quarantined for this problem key (``repro.resilience``
    circuit breaker open after a failure) are excluded, so the planner
    routes around a benched engine until its cooldown admits a probe.
    When quarantine would empty the list, the ``reliable``-marked rungs
    (``stockham``/``reference_x64``) come back regardless — the ladder
    must always have a bottom.
    """
    from repro.engines import iter_engines  # lazy: engines is the leaf layer

    specs = tuple(s for s in iter_engines() if s.supports(key))
    if not specs:
        scope = f" under backend scope {key.backends}" if key.backends else ""
        raise ValueError(
            f"no registered engine supports kind {key.kind!r} at precision "
            f"{key.precision!r}{scope} on backend {key.backend!r}; "
            f"registered engines: {tuple(s.name for s in iter_engines())}"
        )
    breaker = quarantine()
    healthy = tuple(
        s.name for s in specs if not breaker.excluded(s.name, key)
    )
    if healthy:
        return healthy
    reliable = tuple(s.name for s in specs if s.reliable)
    return reliable or tuple(s.name for s in specs)


def _transform_geometry(key: ProblemKey) -> Tuple[int, int, int]:
    """(n, rows_per_frame, n_transforms_total) for the 1D passes of ``key``.

    2D kinds do a length-W pass over H rows and a length-H pass over W
    columns; we model the dominant cost with the last-axis length and
    total 1D transforms across both passes.
    """
    shape = key.shape
    if key.kind in ("fft1d", "rfft1d"):
        n = shape[-1]
        batch = int(np.prod(shape[:-1], dtype=np.int64)) if len(shape) > 1 else 1
        return n, 1, max(batch, 1)
    h, w = shape[-2], shape[-1]
    lead = int(np.prod(shape[:-2], dtype=np.int64)) if len(shape) > 2 else 1
    # rows pass: lead*h transforms of length w; cols pass: lead*w of length h.
    # Use the geometric-mean length so non-square frames aren't mismodelled.
    n = int(2 ** round((math.log2(w) + math.log2(h)) / 2))
    return n, h, max(lead, 1) * (h + w)


def _stage_passes(stages: int, radix: int) -> int:
    """Butterfly passes over the data at the engine's ``radix``."""
    if radix <= 2:
        return stages
    return max(1, math.ceil(stages / math.log2(radix)))


def estimate_variant_time(key: ProblemKey, variant: str) -> float:
    """Roofline-model execution time (seconds) of one call under ``variant``.

    All per-engine coefficients — traffic factor, per-stage overhead, FLOP
    scale, fixed entry cost, radix, fusion — come from the engine's
    registered :class:`repro.engines.CostHints`, so a new registration is
    rankable by ESTIMATE without touching this function.
    """
    from repro.engines import get_engine  # lazy: engines is the leaf layer

    spec = get_engine(variant)
    n, _, n_transforms = _transform_geometry(key)
    counts = butterfly_counts(n, proposed=True)
    stages = counts["stages"]
    passes = _stage_passes(stages, spec.radix)
    # (N/2)·log2 N butterfly passes per transform (paper Tables 1 & 2).
    flops = _FLOPS_PER_BUTTERFLY * counts["butterfly_units"] * stages * n_transforms
    flops *= spec.cost.flop_scale
    # Bytes per element: re+im at the key's precision (f32 pairs = 8 B,
    # f64 pairs = 16 B — the double path moves twice the traffic).
    elem_bytes = 16.0 if key.precision == "double" else 8.0
    on_tpu = key.backend == "tpu"
    if spec.fused and on_tpu:
        # Whole transform on one VMEM residency: one HBM read + one write.
        # Frames over the VMEM budget take the unfused row/turn/column
        # failover instead — three round trips, not one.
        trips = 1
        if key.kind in ("fft2d", "rfft2d") and len(key.shape) >= 2:
            from repro.kernels.fft_radix2 import fft2_fits_vmem  # lazy

            arrays = 6 if key.kind == "rfft2d" else 8
            if not fft2_fits_vmem(key.shape[-2], key.shape[-1], arrays=arrays):
                trips = 3
        traffic = spec.cost.traffic_factor * elem_bytes * n * trips * n_transforms
    else:
        # jnp engines — and fused kernels in interpret mode, which execute
        # as plain XLA ops and get no HBM fusion win.
        traffic = spec.cost.traffic_factor * elem_bytes * n * passes * n_transforms
    if key.kind in _REAL_KINDS:
        # Two-for-one Hermitian pack: one half-size transform, half the bytes.
        flops *= 0.5
        traffic *= 0.5
    # Pencil kind: the corner-turn moves each element once across the mesh.
    collective = 0.0
    if key.kind == "fft2d_pencil" and key.n_devices > 1:
        collective = (
            elem_bytes * float(np.prod(key.shape, dtype=np.int64)) / key.n_devices
        )
    rl = Roofline(
        flops_per_device=flops / key.n_devices,
        bytes_per_device=traffic / key.n_devices,
        collective_bytes_per_device=collective,
        n_devices=key.n_devices,
        model_flops_global=flops,
        peaks=_roofline_peaks(key),
    )
    t = rl.step_time_s * _BACKEND_SLOWDOWN.get(key.backend, 1.0)
    if spec.fused:
        t += _KERNEL_LAUNCH_S
        if not on_tpu:
            t += _INTERPRET_OVERHEAD_S + passes * spec.cost.stage_overhead_s
    else:
        t += passes * spec.cost.stage_overhead_s
    return t + spec.cost.entry_overhead_s


def chunk_candidates(w: int, n_devices: int, limit: int = 16) -> List[int]:
    """Legal corner-turn slab counts: c | W and d | (W/c)."""
    out = [c for c in range(1, limit + 1)
           if w % c == 0 and (w // c) % max(n_devices, 1) == 0]
    return out or [1]


def _estimate_chunks(key: ProblemKey) -> int:
    """Pick the slab count that best overlaps all_to_all with column FFTs.

    Ideal chunking splits the collective into enough slabs that slab i's
    exchange hides behind slab i-1's butterflies; past that, smaller
    slabs just pay more launch latency. We size c ~ collective/compute
    and clamp to the legal divisors.
    """
    w = key.shape[-1]
    cands = chunk_candidates(w, key.n_devices)
    if len(cands) == 1:
        return cands[0]
    compute_s = estimate_variant_time(
        ProblemKey(
            kind="fft2d",
            backend=key.backend,
            device_kind=key.device_kind,
            shape=key.shape,
            dtype=key.dtype,
            n_devices=key.n_devices,
            precision=key.precision,
        ),
        "stockham",
    )
    collective_s = 8.0 * float(np.prod(key.shape, dtype=np.int64)) / (
        key.n_devices * _roofline_peaks(key).ici_link_bw
    )
    ideal = max(1.0, collective_s / max(compute_s, 1e-12))
    # Closest legal slab count to the overlap ideal; ties favour more slabs.
    return min(cands, key=lambda c: (abs(c - ideal), -c))


def _estimate_unroll(key: ProblemKey) -> int:
    """Streaming scan unroll: unroll short pipelines over small frames so
    XLA can interleave frame k's rows with frame k-1's columns across scan
    iterations too; long streams / big frames keep the compact loop."""
    if key.kind != "fft2d_stream" or len(key.shape) < 3:
        return 1
    t = key.shape[0]
    frame_elems = key.shape[-2] * key.shape[-1]
    if t >= 2 and frame_elems <= 128 * 128:
        return 2
    return 1


def oaconv_tile_candidates(key: ProblemKey) -> List[Tuple[int, int]]:
    """Legal FFT tiles for an overlap-save ``oaconv2d`` problem.

    ``key.shape`` ends ``(H, W, KH, KW)`` — image dims then kernel dims.
    Per axis, a tile must be a power of two at least the kernel extent
    (otherwise the overlap-save step ``T - K + 1`` vanishes) and at most
    the padded full-frame transform; jointly, the pair must keep the fused
    kernel's true working set (``repro.kernels.ops.fft2_working_set``)
    inside the VMEM budget. When even the smallest legal tile busts the
    budget (enormous kernels), the single padded full-frame transform is
    the fallback — the engines' unfused failover handles it.
    """
    if len(key.shape) < 4:
        raise ValueError(
            f"oaconv2d keys on (..., H, W, KH, KW); got shape {key.shape}"
        )
    h, w, kh, kw = key.shape[-4:]
    real = not key.dtype.startswith("complex")
    from repro.kernels.ops import fft2_fits_budget  # lazy: pallas import

    def axis_cands(dim: int, k: int) -> List[int]:
        lo, hi = _next_pow2(k), _next_pow2(dim + k - 1)
        return [t for t in (1 << p for p in range(lo.bit_length() - 1,
                                                  hi.bit_length()))
                if lo <= t <= hi]

    pairs = [
        (th, tw)
        for th in axis_cands(h, kh)
        for tw in axis_cands(w, kw)
        if fft2_fits_budget(th, tw, real=real)
    ]
    return pairs or [(_next_pow2(h + kh - 1), _next_pow2(w + kw - 1))]


def _estimate_oaconv_plan(key: ProblemKey) -> FFTPlan:
    """Pick the overlap-save FFT tile with the best modeled time.

    Modeled cost of a tile = (tiles needed to cover the full-size output)
    × (forward + inverse transform of one tile under that tile's best
    schedule). Small tiles waste work on the K−1 overlap; big tiles waste
    it on zero padding and fall off the fused kernel's VMEM cliff — the
    sweet spot is exactly what the census-constrained sweep finds.
    """
    h, w, kh, kw = key.shape[-4:]
    sub_kind = "rfft2d" if not key.dtype.startswith("complex") else "fft2d"
    best: Optional[Tuple[float, str, Tuple[int, int]]] = None
    for th, tw in oaconv_tile_candidates(key):
        sub = ProblemKey(
            kind=sub_kind,
            backend=key.backend,
            device_kind=key.device_kind,
            shape=(th, tw),
            dtype=key.dtype,
            n_devices=key.n_devices,
            precision=key.precision,
            backends=key.backends,
        )
        times = {v: estimate_variant_time(sub, v) for v in variant_candidates(sub)}
        variant = min(times, key=times.get)
        n_tiles = math.ceil((h + kh - 1) / max(th - kh + 1, 1)) * math.ceil(
            (w + kw - 1) / max(tw - kw + 1, 1)
        )
        total = 2.0 * times[variant] * n_tiles  # forward + inverse per tile
        if best is None or total < best[0]:
            best = (total, variant, (th, tw))
    total, variant, tile = best
    return FFTPlan(
        key=key, variant=variant, mode="estimate", est_time_s=total, tile=tile
    )


def estimate_plan(key: ProblemKey) -> FFTPlan:
    """Analytic (FFTW ``ESTIMATE``) plan: no device work, microseconds."""
    if key.kind == "oaconv2d":
        return _estimate_oaconv_plan(key)
    times = {v: estimate_variant_time(key, v) for v in variant_candidates(key)}
    variant = min(times, key=times.get)
    return FFTPlan(
        key=key,
        variant=variant,
        unroll=_estimate_unroll(key),
        chunks=_estimate_chunks(key) if key.kind == "fft2d_pencil" else 1,
        mode="estimate",
        est_time_s=times[variant],
    )


# ------------------------------- MEASURE ---------------------------------


class MeasureTimeout(Exception):
    """A MEASURE candidate exceeded its wall-clock budget (sweep guard)."""


def _time_us(
    fn: Callable,
    x,
    warmup: int = 1,
    iters: int = 5,
    budget_s: Optional[float] = None,
) -> float:
    """Median wall time per call in microseconds (first call = compile).

    ``budget_s`` bounds the candidate's TOTAL wall clock (warmup included):
    past it, :class:`MeasureTimeout` aborts the candidate between calls so
    one pathologically slow schedule cannot hang the whole sweep.
    """
    import jax

    start = time.perf_counter()

    def checkpoint():
        if budget_s is not None and time.perf_counter() - start > budget_s:
            raise MeasureTimeout(
                f"candidate exceeded its {budget_s:.1f}s measure budget"
            )

    for _ in range(max(warmup, 1)):
        jax.block_until_ready(fn(x))
        checkpoint()
    samples = []
    for _ in range(max(iters, 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(x))
        samples.append(time.perf_counter() - t0)
        checkpoint()
    samples.sort()
    return samples[len(samples) // 2] * 1e6


def _measure_input(key: ProblemKey, seed: int = 0):
    """A representative input for ``key``: real for rfft kinds, complex
    else, at the key's precision (a double-precision sweep must move
    double-width bytes or its timings misrepresent the workload); inverse
    real kinds get the half spectrum their runner consumes."""
    import jax.numpy as jnp

    double = key.precision == "double"
    rdt = np.float64 if double else np.float32
    cdt = np.complex128 if double else np.complex64
    rng = np.random.default_rng(seed)
    if key.kind in _REAL_KINDS:
        x = rng.standard_normal(key.shape).astype(rdt)
        if key.direction == "inv":
            x = np.fft.rfft2(x).astype(cdt) if key.kind == "rfft2d" \
                else np.fft.rfft(x).astype(cdt)
    else:
        x = (
            rng.standard_normal(key.shape) + 1j * rng.standard_normal(key.shape)
        ).astype(cdt)
    # measure_plan wraps double sweeps in enable_x64, so this asarray
    # keeps the 64-bit width instead of canonicalizing it away.
    return jnp.asarray(x)


def _candidate_runners(key: ProblemKey) -> Dict[Tuple[str, int], Callable]:
    """(variant, unroll) -> jitted callable for this problem kind."""
    import functools

    import jax

    from repro.core.fft1d import fft_impl, ifft_impl
    from repro.core.fft2d import fft2_impl, fft2_stream, ifft2_impl
    from repro.core.rfft import irfft2_impl, irfft_impl, rfft2_impl, rfft_impl

    inv = key.direction == "inv"
    entry = {
        "fft1d": ifft_impl if inv else fft_impl,
        "fft2d": ifft2_impl if inv else fft2_impl,
        "rfft1d": irfft_impl if inv else rfft_impl,
        "rfft2d": irfft2_impl if inv else rfft2_impl,
    }
    from repro.core.fft1d import BUILTIN_VARIANTS

    runners: Dict[Tuple[str, int], Callable] = {}
    for v in variant_candidates(key):
        if key.kind in entry:
            runners[(v, 1)] = jax.jit(functools.partial(entry[key.kind], variant=v))
        elif key.kind == "fft2d_stream":
            # The scan-unroll knob only exists on the builtin jnp stream;
            # registry engines run their own stream op and would time the
            # identical computation twice under two labels.
            for u in (1, 2) if v in BUILTIN_VARIANTS else (1,):
                runners[(v, u)] = jax.jit(
                    functools.partial(fft2_stream, variant=v, unroll=u)
                )
        else:
            raise ValueError(
                f"MEASURE planning is unavailable for kind {key.kind!r} "
                "(pencil problems need a live mesh; oaconv2d tile choice is "
                "analytic); use mode='estimate' instead"
            )
    return runners


def measure_plan(
    key: ProblemKey,
    warmup: int = 1,
    iters: int = 5,
    timings_out: Optional[Dict[str, float]] = None,
    budget_s: Optional[float] = None,
) -> FFTPlan:
    """Timed candidate sweep (FFTW ``MEASURE``): jit + run every schedule.

    ``timings_out`` (optional dict) receives per-candidate medians in µs,
    keyed ``"variant"`` or ``"variant/unroll=k"`` — benchmarks report it.
    Double-precision keys sweep under ``jax.enable_x64`` so the timed
    calls really trace and move 64-bit data.

    Each candidate gets ``budget_s`` of wall clock (default
    :data:`MEASURE_CANDIDATE_BUDGET_S`); candidates that exceed it — or
    raise — are skipped and recorded in the ``plan.measure`` span rather
    than hanging or killing the sweep. A sweep with no surviving
    candidate returns the ESTIMATE plan with ``degrade_reason``
    ``"measure_timeout"`` (all timed out) or ``"measure_failed"``.
    """
    if budget_s is None:
        budget_s = MEASURE_CANDIDATE_BUDGET_S
    if key.precision == "double":
        from repro._x64 import enable_x64  # lazy

        with enable_x64():
            return _measure_plan_impl(key, warmup, iters, timings_out, budget_s)
    return _measure_plan_impl(key, warmup, iters, timings_out, budget_s)


def _measure_plan_impl(
    key: ProblemKey,
    warmup: int,
    iters: int,
    timings_out: Optional[Dict[str, float]],
    budget_s: float,
) -> FFTPlan:
    import dataclasses

    from repro import obs  # lazy: keep autotune importable without obs users

    x = _measure_input(key)
    best: Optional[Tuple[Tuple[str, int], float]] = None
    timings: Dict[str, float] = {}
    skipped: Dict[str, str] = {}
    # One span for the whole sweep (it is the expensive planner action —
    # under xfft.config(observe=True) it lands in XLA profiles too), with
    # every candidate's median attached to the emitted event.
    with obs.span(
        "plan.measure",
        kind=key.kind,
        shape=key.shape,
        dtype=key.dtype,
        direction=key.direction,
        precision=key.precision,
    ) as out:
        for (variant, unroll), fn in _candidate_runners(key).items():
            label = variant if unroll == 1 else f"{variant}/unroll={unroll}"

            def run(arr, _fn=fn, _variant=variant):
                # plan.measure fault seam fires per timed call, so an
                # injected latency accrues against the candidate budget
                # exactly like a genuinely slow schedule would.
                _faults.maybe_fail(
                    "plan.measure", engine=_variant, kind=key.kind
                )
                return _fn(arr)

            try:
                us = _time_us(run, x, warmup=warmup, iters=iters,
                              budget_s=budget_s)
            except MeasureTimeout:
                skipped[label] = "timeout"
                continue
            except Exception as e:  # noqa: BLE001 — one bad candidate
                skipped[label] = f"error: {e!r}"
                continue
            timings[label] = us
            # Per-candidate event: the calibration ledger's measured
            # prediction for engines the sweep timed but did NOT choose
            # (the chosen one also rides plan.resolve's measured_us).
            obs.emit(
                "plan.measure.candidate",
                engine=variant,
                unroll=unroll,
                label=label,
                kind=key.kind,
                shape=key.shape,
                precision=key.precision,
                median_us=us,
            )
            if timings_out is not None:
                timings_out[label] = us
            if best is None or us < best[1]:
                best = ((variant, unroll), us)
        out["candidates"] = len(timings) + len(skipped)
        out["timings"] = dict(timings)
        if skipped:
            out["skipped"] = dict(skipped)
        if best is None:
            # Nothing survived: fall back to the analytic plan, with the
            # reason recorded on the plan AND in the degrade vocabulary.
            reason = (
                "measure_timeout"
                if any(r == "timeout" for r in skipped.values())
                else "measure_failed"
            )
            out["chosen"] = None
            out["degrade_reason"] = reason
            obs.emit(
                "plan.degrade", kind=key.kind, shape=key.shape,
                direction=key.direction, reason=reason,
            )
            obs.count(f"plan.degrade.{reason}")
            return dataclasses.replace(
                estimate_plan(key), degrade_reason=reason
            )
        (variant, unroll), us = best
        out["chosen"] = variant
        out["chosen_us"] = us
    return FFTPlan(
        key=key,
        variant=variant,
        unroll=unroll,
        chunks=1,
        mode="measure",
        est_time_s=estimate_variant_time(key, variant),
        measured_us=us,
    )
