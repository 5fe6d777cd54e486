"""The degradation ladder: every transform call lands somewhere.

``run_plan`` wraps the engine dispatch of every ``repro.xfft`` transform
and of ``repro.plan.execute``. When the planned engine raises, the
failure is recorded in the quarantine breaker (:mod:`.breaker`), a
``resilience.failover`` obs event names the benched engine, and the call
retries on the next-best healthy rung — ranked by the same analytic
ESTIMATE model the planner uses — bottoming out at the always-works jnp
engines (``stockham``/``reference_x64``). One bad Pallas lowering costs
one failover, not an outage.

The opt-in output-health guard (``xfft.config(check_health="nan")``)
treats a non-finite output the same way: the producing engine takes a
failure, the call retries one rung down. If every rung yields non-finite
values the last output is returned as-is — at that point the *input* is
poisoned and no engine can do better.

Rungs come from the failed plan's own problem key, so a sharded
(``fft2d_pencil``) call fails over only to another engine that serves
that key across its devices, never to a single-device engine that would
gather the grid.

Forced plans (``xfft.config(variant=...)``) bypass the ladder entirely:
a pin is an explicit opinion, and tests that pin an engine must observe
exactly that engine, faults and all.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Set

from repro import obs
from repro.resilience import faults
from repro.resilience.breaker import quarantine

__all__ = ["run_plan"]


def _check_health_enabled() -> bool:
    from repro.xfft._config import get_config  # lazy: xfft sits above plan

    return get_config().check_health == "nan"


def _is_finite(out: Any) -> bool:
    """False only when ``out`` is concretely non-finite.

    Tracers (inside jit) and non-array payloads can't be inspected;
    they count as healthy — the guard is a serving-path feature, not a
    trace-time one.
    """
    try:
        import jax.numpy as jnp

        return bool(jnp.isfinite(out).all())
    except Exception:
        return True


def _engine_meta(variant: str):
    """(backend, requires_x64) for a registered engine, (None, None) for
    builtin-only names the registry does not know."""
    from repro.engines import get_engine

    try:
        spec = get_engine(variant)
    except Exception:
        return None, None
    return spec.backend, spec.requires_x64


def _next_rung(key, attempted: Set[str]) -> Optional[str]:
    """Best untried healthy engine for ``key``, or None at the bottom.

    Candidates come from the planner's own quarantine-filtered
    enumeration, ranked by the analytic ESTIMATE model — the failover
    plan is exactly the plan the planner would have made without the
    benched engine.
    """
    from repro.plan.autotune import estimate_variant_time, variant_candidates

    try:
        names = [v for v in variant_candidates(key) if v not in attempted]
    except ValueError:
        return None
    if not names:
        return None
    return min(names, key=lambda v: estimate_variant_time(key, v))


def run_plan(plan, runner: Callable[[str], Any]):
    """Run ``runner(variant)`` with failover down the engine ladder.

    ``runner`` executes the transform under a named engine (a closure
    over the input array and kwargs). Success records into the breaker —
    closing any half-open probe for (engine, key) — and returns.
    Failure quarantines the engine for this problem key and retries the
    next-best rung; when no rung remains the last error propagates.
    """
    if plan.mode == "forced":
        # Pinned engines are exempt from injection and failover alike:
        # the scope asked for this engine, so this engine is the answer.
        # The dispatch span still fires — forced calls belong in the
        # flight recorder and the calibration ledger like any other.
        backend, x64 = _engine_meta(plan.variant)
        with obs.span(
            "engine.apply", engine=plan.variant, backend=backend,
            kind=plan.key.kind, direction=plan.key.direction,
            shape=plan.key.shape, precision=plan.key.precision, x64=x64,
            n_devices=plan.key.n_devices,
        ) as sp:
            out = runner(plan.variant)
            sp["ok"] = True
        return out
    key = plan.key
    breaker = quarantine()
    variant = plan.variant
    attempted: Set[str] = set()
    check_health = _check_health_enabled()
    unhealthy_out = None
    while True:
        reason = "error"
        err: Optional[BaseException] = None
        try:
            # Injected pre-dispatch failures (error/latency/vmem) fire
            # OUTSIDE the span: a fault that prevented the engine from
            # running must not pollute its observed-duration population.
            faults.maybe_fail(
                "engine.apply", engine=variant, kind=key.kind,
                direction=key.direction,
            )
            backend, x64 = _engine_meta(variant)
            with obs.span(
                "engine.apply", engine=variant, backend=backend,
                kind=key.kind, direction=key.direction, shape=key.shape,
                precision=key.precision, x64=x64, n_devices=key.n_devices,
            ) as sp:
                out = faults.maybe_corrupt(
                    "engine.apply", runner(variant), engine=variant,
                    kind=key.kind, direction=key.direction,
                )
                sp["ok"] = True
            if not check_health or _is_finite(out):
                breaker.record_success(variant, key)
                return out
            reason = "nonfinite"
            unhealthy_out = out
        except Exception as e:  # noqa: BLE001 — the ladder exists to catch
            err = e
        attempted.add(variant)
        opened = breaker.record_failure(variant, key, error=repr(err or reason))
        nxt = _next_rung(key, attempted)
        obs.emit(
            "resilience.failover",
            engine=variant,
            kind=key.kind,
            shape=key.shape,
            direction=key.direction,
            reason=reason,
            error=repr(err) if err is not None else None,
            next=nxt,
            quarantined=opened,
        )
        obs.count("resilience.failover")
        if nxt is None:
            if err is not None:
                raise err
            # Non-finite on the bottom rung: the input itself is poisoned;
            # returning the output beats raising for a health *guard*.
            return unhealthy_out
        variant = nxt
