"""Event-stream exporters: JSONL and Prometheus exposition.

The obs substrate records decisions; this module makes them *legible to
standard tooling* without taking a single dependency:

* :func:`write_jsonl` — one JSON object per event, the flight-recorder
  dump format (replayable, greppable, diffable);
* :func:`prometheus_text` / :func:`write_prometheus` — text exposition
  of the process-wide counters, gauges, and latency-histogram quantiles
  in the format every metrics scraper already parses.

Everything here is pure formatting over snapshots — no locks held while
writing, no imports from plan/engines/serve. For a timeline, the profiler's
own trace carries the program's spans, with their ids, beside the device's
operations (``repro.obs.span`` under profiling).
"""

from __future__ import annotations

import json
from typing import Any, Dict, Iterable, List, Mapping, Optional

from repro.obs.hist import LatencyHistogram
from repro.obs.record import Event

__all__ = [
    "event_dict",
    "prometheus_text",
    "write_jsonl",
    "write_prometheus",
]


def _jsonable(v: Any) -> Any:
    """Coerce a field value to something json.dump accepts (repr fallback:
    a dump must never fail because an event carried an exotic object)."""
    if v is None or isinstance(v, (bool, int, float, str)):
        return v
    if isinstance(v, (list, tuple)):
        return [_jsonable(x) for x in v]
    if isinstance(v, Mapping):
        return {str(k): _jsonable(x) for k, x in v.items()}
    return repr(v)


def event_dict(event: Event) -> Dict[str, Any]:
    """One event as a JSON-safe dict (the JSONL line schema)."""
    return {
        "name": event.name,
        "t": event.t,
        "tid": event.tid,
        "fields": {str(k): _jsonable(v) for k, v in event.fields.items()},
    }


def write_jsonl(events: Iterable[Event], path: str) -> str:
    """Write ``events`` to ``path`` as JSON Lines; returns the path."""
    with open(path, "w") as fh:
        for event in events:
            fh.write(json.dumps(event_dict(event)) + "\n")
    return path


# ------------------------------ Prometheus ---------------------------------


def _label_value(value: Any) -> str:
    s = str(value)
    return s.replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


def prometheus_text(
    counters: Optional[Mapping[str, int]] = None,
    gauges: Optional[Mapping[str, float]] = None,
    histograms: Optional[Mapping[str, LatencyHistogram]] = None,
) -> str:
    """Render counters, gauges, and histogram quantiles as Prometheus
    text exposition (counters under one ``repro_events_total`` family,
    histograms as summary-style quantile series in microseconds)."""
    lines: List[str] = []
    if counters:
        lines.append("# TYPE repro_events_total counter")
        for name, value in sorted(counters.items()):
            lines.append(
                f'repro_events_total{{event="{_label_value(name)}"}} {int(value)}'
            )
    if gauges:
        lines.append("# TYPE repro_gauge gauge")
        for name, value in sorted(gauges.items()):
            lines.append(
                f'repro_gauge{{name="{_label_value(name)}"}} {float(value)}'
            )
    if histograms:
        lines.append("# TYPE repro_latency_us summary")
        for name, h in sorted(histograms.items()):
            label = _label_value(name)
            for q in (50, 95, 99):
                lines.append(
                    f'repro_latency_us{{hist="{label}",quantile="0.{q}"}} '
                    f"{h.percentile(q)}"
                )
            lines.append(f'repro_latency_us_count{{hist="{label}"}} {h.count}')
            lines.append(f'repro_latency_us_sum{{hist="{label}"}} {h.sum_us}')
    return "\n".join(lines) + ("\n" if lines else "")


def write_prometheus(
    path: str,
    counters: Optional[Mapping[str, int]] = None,
    gauges: Optional[Mapping[str, float]] = None,
    histograms: Optional[Mapping[str, LatencyHistogram]] = None,
) -> str:
    """Write :func:`prometheus_text` to ``path``; returns the path."""
    with open(path, "w") as fh:
        fh.write(prometheus_text(counters=counters, gauges=gauges,
                                 histograms=histograms))
    return path
