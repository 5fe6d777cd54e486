"""repro.obs — structured tracing, metrics and plan introspection.

The software analog of the paper's control unit + RAM controller
*accounting*: every decision point in the stack — planner resolution
(``repro.plan``), MEASURE sweeps, engine dispatch (``repro.engines``),
fused-kernel VMEM failovers (``repro.kernels``), wisdom load/save, and
service batching (``repro.serve``: queue intake ``serve.queue``, scheduler
heartbeats ``serve.loop.tick`` with queue-depth gauges, per-lane batch
spans ``serve.batch``, quarantine-driven re-resolution
``serve.lane.replan``, wisdom warm starts ``serve.wisdom.warm_start``) —
emits structured events through this package.

    from repro import obs
    import repro.xfft as xfft

    with obs.capture() as trace:
        xfft.fft2(x)                       # cold: plan miss
        xfft.fft2(x)                       # warm: plan hit
    [e["outcome"] for e in trace.select("plan.resolve")]  # ['miss', 'hit']

Process-wide counters stay on even without a capture scope (one dict
increment per event — the ``benchmarks/obs_bench.py`` gate holds the
instrumented hot path within 3% of uninstrumented); ``xfft.report()``
renders them next to the live plan cache, FFTW ``export_wisdom``-style.

Always-on telemetry rides the sink hook (:mod:`repro.obs.telemetry`,
installed at import): a bounded **flight recorder** keeps the most
recent events with no capture scope open and dumps a JSONL snapshot
when a failure trigger fires, and a **calibration ledger** joins planner
predictions against observed engine dispatch times. Latency histograms
(:mod:`repro.obs.hist`) and exporters — JSONL and Prometheus text
(:mod:`repro.obs.export`) — make all of it consumable by standard
tooling.

Under profiling (``xfft.config(observe=True)`` or
``capture(profile=True)``) each span also becomes a
``jax.profiler.TraceAnnotation`` carrying its ``span_id`` and
``call_id``, and a :class:`SpanRecord` in a bounded buffer read by
:func:`spans`: the program's own host timeline, joinable to the
profiler's device trace.
"""

from repro.obs import export, hist, telemetry
from repro.obs.hist import (
    LatencyHistogram,
    histogram,
    histograms,
    reset_histograms,
)
from repro.obs.record import (
    Event,
    SpanRecord,
    Trace,
    add_sink,
    capture,
    count,
    counters,
    emit,
    enabled,
    pop_observe,
    profiling,
    push_observe,
    remove_sink,
    reset_counters,
    reset_spans,
    span,
    spans,
)
from repro.obs.telemetry import (
    CalibrationLedger,
    FlightRecorder,
    calibration_ledger,
    flight_recorder,
    set_flight_recorder,
)

__all__ = [
    "CalibrationLedger",
    "Event",
    "FlightRecorder",
    "LatencyHistogram",
    "SpanRecord",
    "Trace",
    "add_sink",
    "calibration_ledger",
    "capture",
    "count",
    "counters",
    "emit",
    "enabled",
    "export",
    "flight_recorder",
    "hist",
    "histogram",
    "histograms",
    "pop_observe",
    "profiling",
    "push_observe",
    "remove_sink",
    "reset_counters",
    "reset_histograms",
    "reset_spans",
    "set_flight_recorder",
    "span",
    "spans",
    "telemetry",
]

# Always-on by default: the black box records from the first import.
telemetry.install_default()
