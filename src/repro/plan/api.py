"""Planner entry points: ``plan_fft`` / ``execute`` / ``resolve_call``.

``plan_fft`` is the explicit front door (pick a mode, get a plan, it is
cached — and persisted when the cache is file-backed). ``resolve_call``
is the implicit one: every ``repro.xfft`` transform and every
``variant="auto"`` call site in ``repro.core`` funnels through it, so a
warm cache (e.g. MEASURE plans produced at service startup or by
``benchmarks/plan_autotune.py``) steers the hot path while a cold cache
falls back to the analytic ESTIMATE model. ``resolve_call`` is also
where the scoped ``repro.xfft.config`` overrides land: a forced variant,
a measure-on-miss mode, or a wisdom directory apply to every call inside
the scope without any signature changing. ``resolve`` is the pre-xfft
spelling of the same lookup, kept for callers that plan bare problems.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Dict, Optional, Tuple

from repro import obs
from repro.plan.autotune import estimate_plan, measure_plan
from repro.plan.cache import PlanCache, default_cache
from repro.plan.plan import FFTPlan, ProblemKey, problem_key
from repro.resilience.breaker import quarantine
from repro.resilience.ladder import run_plan

__all__ = ["plan_fft", "execute", "resolve", "resolve_call"]

#: Kinds whose MEASURE mode degrades to ESTIMATE: pencil problems need a
#: live mesh to time; oaconv2d tile choice is analytic by construction.
_ESTIMATE_ONLY_KINDS = ("fft2d_pencil", "oaconv2d")


def plan_fft(
    kind: str,
    shape: Tuple[int, ...],
    dtype: str = "complex64",
    mode: str = "estimate",
    n_devices: int = 1,
    cache: Optional[PlanCache] = None,
    force: bool = False,
    measure_iters: int = 5,
    timings_out: Optional[Dict[str, float]] = None,
    direction: str = "fwd",
    axes: Optional[Tuple[int, ...]] = None,
    precision: str = "single",
    backends: Tuple[str, ...] = (),
) -> FFTPlan:
    """Plan one FFT problem; consult the cache first unless ``force``.

    ``mode="estimate"`` is analytic and instant; ``mode="measure"`` jits
    and times every candidate schedule (pencil problems stay analytic —
    timing them needs a live mesh; ``oaconv2d`` tile selection is analytic
    too). A MEASURE result replaces a cached ESTIMATE plan for the same
    key. File-backed caches are saved after every new plan so a second
    process re-tunes nothing.

    ``direction="inv"`` plans the inverse transform, which tunes under its
    own cache key (forward wisdom never cross-contaminates it). ``axes``
    is part of the key too; the ``norm`` convention is not — it is applied
    as a scale outside the engine, so all conventions share one entry.
    ``precision`` and ``backends`` restrict which registered engines the
    planner may consider (``repro.engines``) and are part of the key.
    """
    if mode not in ("estimate", "measure"):
        raise ValueError(f"mode must be 'estimate' or 'measure', got {mode!r}")
    with obs.span("plan.resolve", entry="plan_fft") as out:
        cache = cache if cache is not None else default_cache()
        key = problem_key(kind, shape, dtype, n_devices, direction, axes,
                          precision, backends)
        # Pencil problems can't be timed without a live mesh, and oaconv2d tile
        # selection is a closed-form working-set/efficiency trade-off: the best
        # we can do is the analytic model, so a cached ESTIMATE plan already is
        # the answer for both kinds.
        effective_mode = "estimate" if kind in _ESTIMATE_ONLY_KINDS else mode
        degrade = _degrade_event(key, mode, effective_mode, "estimate_only_kind")
        if not force:
            hit = cache.get(key)
            if hit is not None and (effective_mode == "estimate" or hit.mode == "measure"):
                _resolve_event(out, key, mode, "hit", hit, cache)
                return hit
        if effective_mode == "measure":
            plan = measure_plan(key, iters=measure_iters, timings_out=timings_out)
            outcome = "measured"
        else:
            plan = estimate_plan(key)
            outcome = "miss"
            if degrade is not None:
                plan = dataclasses.replace(plan, degrade_reason=degrade)
        cache.put(plan)
        if cache.path:
            cache.save()
        _resolve_event(out, key, mode, outcome, plan, cache)
        return plan


def _degrade_event(
    key: ProblemKey, requested_mode: str, effective_mode: str, reason: str
) -> Optional[str]:
    """Emit+count a MEASURE->ESTIMATE degrade; returns the reason or None.

    The record the ROADMAP's wisdom-shipping story needs: a fleet whose
    plans never tune should be able to read *why* (pencil/oaconv kinds
    are analytic by construction, a jit trace forbids timing, a forced
    variant makes timing pointless) instead of inferring it from silence.
    """
    if requested_mode != "measure" or effective_mode == "measure":
        return None
    obs.emit(
        "plan.degrade",
        kind=key.kind,
        shape=key.shape,
        direction=key.direction,
        reason=reason,
    )
    obs.count(f"plan.degrade.{reason}")
    return reason


def _resolve_event(
    out: Dict,
    key: ProblemKey,
    mode: str,
    outcome: str,
    plan: FFTPlan,
    cache: Optional[PlanCache],
) -> None:
    """Fill the decision's ``plan.resolve`` span (``out``, its extra
    fields) and count the outcome.

    ``outcome`` is the cache verdict: ``"hit"`` (cached plan served),
    ``"miss"`` (fresh ESTIMATE), ``"measured"`` (a timed sweep ran),
    ``"forced"`` (a scoped variant pin replaced the planned engine).
    """
    obs.count(f"plan.resolve.{outcome}")
    out.update(
        kind=key.kind,
        shape=key.shape,
        dtype=key.dtype,
        direction=key.direction,
        precision=key.precision,
        backend=key.backend,
        mode=mode,
        outcome=outcome,
        variant=plan.variant,
        plan_mode=plan.mode,
        est_time_s=plan.est_time_s,
        measured_us=plan.measured_us,
        degrade_reason=plan.degrade_reason,
        cache_path=getattr(cache, "path", None),
        key=key.cache_key(),
    )


def _active_config():
    """The scoped ``repro.xfft.config`` state (lazy import: xfft uses plan)."""
    from repro.xfft._config import get_config

    return get_config()


#: PlanCache instances memoized per config ``cache_dir`` so repeated calls
#: under the same scope accumulate hits in ONE cache (and one wisdom file).
_DIR_CACHES: Dict[str, PlanCache] = {}


def _cache_for_dir(cache_dir: str) -> PlanCache:
    path = os.path.join(cache_dir, "xfft_plans.json")
    cache = _DIR_CACHES.get(path)
    if cache is None:
        cache = _DIR_CACHES.setdefault(path, PlanCache(path=path))
    return cache


_WARNED_NO_TRACE_INTROSPECTION = False


def _trace_safe() -> bool:
    """True when no JAX trace is in flight (MEASURE may jit and time).

    Unavailable introspection degrades to False — a measure-mode config
    then falls back to ESTIMATE rather than risking a jit inside a trace
    — and says so once, so autotuning never stops working silently after
    a jax upgrade.
    """
    import warnings

    with warnings.catch_warnings():
        # newer jax deprecates the jax.core re-export; stay silent so
        # callers running with -W error::DeprecationWarning never trip
        warnings.simplefilter("ignore", DeprecationWarning)
        try:
            from jax.core import trace_state_clean
        except Exception:  # pragma: no cover - public re-export removed
            try:
                from jax._src.core import trace_state_clean
            except Exception:
                global _WARNED_NO_TRACE_INTROSPECTION
                if not _WARNED_NO_TRACE_INTROSPECTION:
                    _WARNED_NO_TRACE_INTROSPECTION = True
                    warnings.warn(
                        "jax trace-state introspection unavailable on this "
                        "jax version; mode='measure' resolution degrades to "
                        "ESTIMATE (use plan_fft(mode='measure') to tune "
                        "explicitly)",
                        RuntimeWarning,
                        stacklevel=3,
                    )
                return False
        try:
            return bool(trace_state_clean())
        except Exception:  # pragma: no cover - conservative inside traces
            return False


def resolve_call(
    kind: str,
    shape: Tuple[int, ...],
    dtype: str = "complex64",
    n_devices: int = 1,
    cache: Optional[PlanCache] = None,
    direction: str = "fwd",
    axes: Optional[Tuple[int, ...]] = None,
    mode: Optional[str] = None,
) -> FFTPlan:
    """Resolve one transform *call* to a concrete plan, config applied.

    The dispatch pipeline of every ``repro.xfft`` entry point (and of the
    legacy ``variant="auto"`` call sites):

    1. The active :func:`repro.xfft.config` scope supplies defaults: its
       ``cache_dir`` selects the wisdom cache (else the process-wide
       default cache), its ``mode`` decides what a cache miss costs, and
       its ``precision``/``backend`` constraints become part of the
       problem key — the planner then only considers registered engines
       (``repro.engines``) capable of that precision on those backends,
       and wisdom tuned under one constraint set never serves another.
    2. Cache hit -> the cached (possibly MEASURE) plan. Miss -> ESTIMATE,
       which is pure Python on analytic counts and therefore safe while
       JAX is tracing the surrounding computation. ``mode="measure"``
       upgrades misses (and cached ESTIMATE plans) to a timed sweep, but
       only outside a trace — inside one it degrades to ESTIMATE rather
       than jitting mid-trace.
    3. A scoped ``variant=...`` override replaces the planned schedule
       (the returned plan is marked ``mode="forced"`` and never cached:
       forced choices are opinions, not wisdom).

    Resilience: a cached plan whose engine is quarantined for this key
    (``repro.resilience`` circuit breaker open after a failure) is NOT
    served — the call re-resolves with quarantined engines excluded from
    the candidate sweep (outcome ``"quarantined"``), and the fallback
    plan is never written into the cache: wisdom must outlive the bench,
    the workaround must not.

    The decision is timed as the ``plan.resolve`` span, whose event
    carries the outcome, the key and the chosen plan.
    """
    with obs.span("plan.resolve", entry="resolve_call") as out:
        cfg = _active_config()
        if cache is None:
            cache = _cache_for_dir(cfg.cache_dir) if cfg.cache_dir else default_cache()
        key = problem_key(kind, shape, dtype, n_devices, direction, axes,
                          cfg.precision, cfg.backends)
        mode = mode if mode is not None else cfg.mode
        breaker = quarantine()
        plan = cache.get(key)
        hit = plan is not None
        quarantined = hit and breaker.excluded(plan.variant, key)
        if quarantined:
            plan = None  # re-resolve around the benched engine
        affected = quarantined or breaker.affects(key)
        # A forced variant discards the planner's pick, so never pay a timed
        # sweep inside the scope — the pin exists to skip planning costs.
        # Either degrade (a variant pin, an analytic-only kind, a dirty trace)
        # is recorded as a plan.degrade event AND — for fresh plans — on the
        # plan's own degrade_reason, so wisdom files say why they are ESTIMATE.
        degrade = None
        if mode == "measure" and (plan is None or plan.mode != "measure"):
            if cfg.variant is not None:
                degrade = "forced_variant"
            elif kind in _ESTIMATE_ONLY_KINDS:
                degrade = "estimate_only_kind"
            elif affected:
                # Sweeping while an engine is benched would tune (and persist)
                # wisdom over a temporarily reduced engine population.
                degrade = "engine_quarantined"
        want_measure = (
            mode == "measure"
            and degrade is None
            and (plan is None or plan.mode != "measure")
            # A measure_timeout plan means the sweep already hung once for
            # this key; don't re-hang every call — plan_fft(force=True) is
            # the explicit re-tune path.
            and (plan is None or plan.degrade_reason != "measure_timeout")
        )
        measured = False
        if want_measure and not _trace_safe():
            degrade = "trace_not_clean"
            want_measure = False
        if degrade is not None:
            _degrade_event(key, "measure", "estimate", degrade)
        if want_measure:
            plan = cache.put(measure_plan(key))
            measured = True
            if cache.path:
                cache.save()
        elif plan is None:
            # ESTIMATE results stay in memory only: they are free to recompute,
            # and a whole-file save here could clobber wisdom another process
            # measured into the same file after we loaded it (it would also put
            # file I/O inside jit traces). Only MEASURE results earn a write.
            fresh = estimate_plan(key)
            if degrade is not None:
                fresh = dataclasses.replace(fresh, degrade_reason=degrade)
            # Plans resolved under an active quarantine are workarounds, not
            # wisdom: keep them out of the cache so the planned engine comes
            # back the moment its breaker closes.
            plan = fresh if affected else cache.put(fresh)
        if cfg.variant is not None and cfg.variant != plan.variant:
            # The key (and therefore plan.precision) already carries the scoped
            # precision; only the engine choice itself can be forced.
            plan = dataclasses.replace(
                plan, variant=cfg.variant, mode="forced", measured_us=None,
                degrade_reason=degrade,
            )
            _resolve_event(out, key, mode, "forced", plan, cache)
            return plan
        outcome = (
            "quarantined" if quarantined
            else "measured" if measured
            else "hit" if hit
            else "miss"
        )
        _resolve_event(out, key, mode, outcome, plan, cache)
        return plan


def resolve(
    kind: str,
    shape: Tuple[int, ...],
    dtype: str = "complex64",
    n_devices: int = 1,
    cache: Optional[PlanCache] = None,
    direction: str = "fwd",
) -> FFTPlan:
    """Cheap plan lookup for ``variant="auto"`` call sites (trace-safe).

    Pre-xfft spelling of :func:`resolve_call` under the kind's canonical
    axes; kept so bare-problem callers read naturally.
    """
    return resolve_call(kind, shape, dtype, n_devices, cache, direction)


def execute(plan: FFTPlan, x, mesh=None, axis: str = "data"):
    """Run ``x`` through the transform ``plan`` was made for.

    Pencil plans need the ``mesh`` (and device-axis name) the plan's
    ``n_devices`` refers to.

    Single-device kinds run through the resilience degradation ladder
    (:func:`repro.resilience.run_plan`): an engine failure is quarantined
    and the call retries the next-best healthy rung instead of raising.
    The pencil and oaconv2d composites dispatch directly — their variants
    compose per-pass engines that each ladder on their own.
    """
    kind = plan.key.kind
    inv = plan.key.direction == "inv"
    if kind == "fft1d":
        from repro.core.fft1d import fft_impl, ifft_impl

        impl = ifft_impl if inv else fft_impl
        return run_plan(plan, lambda v: impl(x, variant=v))
    if kind == "fft2d":
        from repro.core.fft2d import fft2_impl, ifft2_impl

        impl = ifft2_impl if inv else fft2_impl
        return run_plan(plan, lambda v: impl(x, variant=v))
    if kind == "rfft1d":
        from repro.core.rfft import irfft_impl, rfft_impl

        impl = irfft_impl if inv else rfft_impl
        return run_plan(plan, lambda v: impl(x, variant=v))
    if kind == "rfft2d":
        from repro.core.rfft import irfft2_impl, rfft2_impl

        impl = irfft2_impl if inv else rfft2_impl
        return run_plan(plan, lambda v: impl(x, variant=v))
    if kind == "fft2d_stream":
        from repro.core.fft2d import fft2_stream

        return run_plan(
            plan, lambda v: fft2_stream(x, variant=v, unroll=plan.unroll)
        )
    if kind == "fft2d_pencil":
        if mesh is None:
            raise ValueError("execute() needs mesh=... for a pencil plan")
        from repro.core.distributed import fft2_pencil_overlapped

        return fft2_pencil_overlapped(
            x, mesh, axis=axis, variant=plan.variant, chunks=plan.chunks
        )
    if kind == "oaconv2d":
        from repro.imaging.tiled import oaconvolve2

        if not (isinstance(x, (tuple, list)) and len(x) == 2):
            raise ValueError(
                "execute() needs x=(image, kernel) for an oaconv2d plan"
            )
        image, kernel = x
        return oaconvolve2(image, kernel, tile=plan.tile)
    raise ValueError(f"plan has unknown kind {kind!r}")
