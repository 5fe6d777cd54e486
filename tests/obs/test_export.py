"""Exporters: JSONL round-trip, Prometheus text."""

import json

from repro.obs.export import (
    event_dict,
    prometheus_text,
    write_jsonl,
    write_prometheus,
)
from repro.obs.hist import LatencyHistogram
from repro.obs.record import Event


def _ev(name, t=1.0, tid=11, **fields):
    return Event(name=name, t=t, fields=fields, tid=tid)


def test_jsonl_round_trip(tmp_path):
    events = [
        _ev("plan.resolve", t=1.0, outcome="hit", shape=(64, 64)),
        _ev("engine.apply", t=2.0, duration_us=120.5, ok=True),
    ]
    path = write_jsonl(events, str(tmp_path / "events.jsonl"))
    lines = [json.loads(line) for line in open(path)]
    assert [ln["name"] for ln in lines] == ["plan.resolve", "engine.apply"]
    assert lines[0]["fields"]["shape"] == [64, 64]
    assert lines[1]["fields"]["duration_us"] == 120.5
    assert all(ln["tid"] == 11 for ln in lines)


def test_event_dict_survives_exotic_field_values():
    class Weird:
        def __repr__(self):
            return "<weird>"

    d = event_dict(_ev("x", obj=Weird(), nested={"a": (1, Weird())}, none=None))
    json.dumps(d)  # must be serialisable no matter what rode the event
    assert d["fields"]["obj"] == "<weird>"
    assert d["fields"]["nested"]["a"] == [1, "<weird>"]
    assert d["fields"]["none"] is None


def test_prometheus_counters_gauges_and_quantiles(tmp_path):
    h = LatencyHistogram()
    for v in (10.0, 100.0, 1000.0):
        h.record(v)
    text = prometheus_text(
        counters={"plan.resolve.hit": 3},
        gauges={"flight_recorder_retained": 42},
        histograms={"serve.lane.spectrum.x": h},
    )
    assert '# TYPE repro_events_total counter' in text
    assert 'repro_events_total{event="plan.resolve.hit"} 3' in text
    assert 'repro_gauge{name="flight_recorder_retained"} 42.0' in text
    assert 'quantile="0.50"' in text and 'quantile="0.99"' in text
    assert 'repro_latency_us_count{hist="serve.lane.spectrum.x"} 3' in text
    assert text.endswith("\n")
    path = write_prometheus(
        str(tmp_path / "metrics.prom"), counters={"a": 1}
    )
    assert 'repro_events_total{event="a"} 1' in open(path).read()


def test_prometheus_escapes_label_values():
    text = prometheus_text(counters={'weird"name\\x': 1})
    assert 'event="weird\\"name\\\\x"' in text


def test_prometheus_empty_is_empty():
    assert prometheus_text() == ""
