"""Obs telemetry under the serve loop: thread isolation, bounded ring,
per-lane latency histograms on the tick stream.

The flight recorder is process-wide by design — a serving fleet member's
black box must see the background scheduler thread's events. The capture
stack is contextvars-scoped by design — a caller's ``capture()`` window
must NOT see them. These tests pin both properties at once, plus the
ring staying bounded under sustained emission and the lane histograms
feeding ``serve.loop.tick`` gauges.
"""

import threading

import pytest

from repro import obs
from repro.obs import telemetry
from repro.obs.hist import histogram, reset_histograms
from repro.obs.telemetry import FlightRecorder
from repro.serve import BatchPolicy, LaneKey, ServeLoop


@pytest.fixture
def recorder(tmp_path):
    rec = FlightRecorder(capacity=128, dump_dir=str(tmp_path / "flight"))
    prev = telemetry.set_flight_recorder(rec)
    yield rec
    telemetry.set_flight_recorder(prev)


@pytest.fixture(autouse=True)
def _clean_hists():
    reset_histograms()
    yield
    reset_histograms()


def _toy_loop(**kw):
    def classify(r):
        return LaneKey(r["lane"], ())

    def execute(lane, members):
        for m in members:
            m["served"] = True

    return ServeLoop(classify, execute, service="toy", **kw)


def test_background_thread_events_reach_recorder_not_caller_capture(recorder):
    loop = _toy_loop(batch=BatchPolicy(max_batch=4, max_wait_s=0.0))
    loop.start()
    try:
        with obs.capture() as trace:
            tickets = [loop.submit({"lane": "a", "i": i, "served": False})
                       for i in range(8)]
            for t in tickets:
                assert t.result(timeout=5.0)["served"]
    finally:
        loop.stop()
    # the caller's window saw its own submits...
    assert len(trace.select("serve.loop.enqueue")) == 8
    # ...but NOT the scheduler thread's dispatch events
    assert trace.select("serve.loop.tick") == []
    # which the process-wide flight recorder did record,
    ring = recorder.events()
    ticks = [e for e in ring if e.name == "serve.loop.tick"]
    assert ticks and all(e["service"] == "toy" for e in ticks)
    # on a distinct thread, named in the recorder's thread-name map
    loop_tids = {e.tid for e in ticks}
    caller_tid = threading.get_ident()
    assert caller_tid not in loop_tids
    names = recorder.thread_names()
    assert any("serve-loop[toy]" in names[tid] for tid in loop_tids)


def test_ring_stays_bounded_under_sustained_loop_emission(recorder):
    loop = _toy_loop(batch=BatchPolicy(max_batch=2, max_wait_s=0.0))
    loop.start()
    try:
        for wave in range(20):
            tickets = [loop.submit({"lane": "a", "i": i, "served": False})
                       for i in range(16)]
            for t in tickets:
                t.result(timeout=5.0)
            assert len(recorder.events()) <= recorder.capacity
    finally:
        loop.stop()
    stats = recorder.stats()
    assert stats["retained"] <= stats["capacity"] == 128
    # sustained traffic really flowed through the bounded ring
    assert stats["recorded_total"] > 128


def test_lane_error_in_background_thread_dumps_flight_snapshot(recorder):
    def classify(r):
        return LaneKey(r["lane"], ())

    def execute(lane, members):
        raise RuntimeError("executor exploded")

    loop = ServeLoop(classify, execute, service="toy",
                     batch=BatchPolicy(max_batch=1, max_wait_s=0.0))
    loop.start()
    try:
        ticket = loop.submit({"lane": "a"})
        with pytest.raises(RuntimeError):
            ticket.result(timeout=5.0)
    finally:
        loop.stop()
    dumps = recorder.stats()["dumps"]
    assert any(d["trigger"] == "serve.lane.error" for d in dumps)


def test_tick_latency_lands_in_lane_histogram():
    class Clock:
        now = 0.0

        def __call__(self):
            return self.now

    clock = Clock()
    loop = _toy_loop(batch=BatchPolicy(max_batch=8), clock=clock)
    for i in range(4):
        loop.submit({"lane": "a", "i": i, "served": False})
    clock.now = 0.002  # tickets waited 2 ms admission -> completion
    assert loop.tick(drain=True) == 4
    h = histogram("serve.lane.toy.a[]")
    assert h.count == 4
    assert h.bucket_index(h.percentile(50)) == h.bucket_index(2000.0)


def test_tick_event_carries_lane_tail_gauges():
    loop = _toy_loop(batch=BatchPolicy(max_batch=4))
    with obs.capture() as trace:
        for i in range(4):
            loop.submit({"lane": "a", "i": i, "served": False})
        loop.tick(drain=True)
        for i in range(4):
            loop.submit({"lane": "a", "i": i, "served": False})
        loop.tick(drain=True)
    first, second = trace.select("serve.loop.tick")
    # gauges reflect the histogram as of the PREVIOUS batches
    assert first["lane_n"] == 0 and first["lane_p99_us"] is None
    assert second["lane_n"] == 4
    assert second["lane_p50_us"] > 0 and second["lane_p99_us"] > 0


def test_failed_batches_stay_out_of_latency_histograms():
    def classify(r):
        return LaneKey(r["lane"], ())

    calls = {"n": 0}

    def execute(lane, members):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("first batch fails")

    loop = ServeLoop(classify, execute, service="toy",
                     batch=BatchPolicy(max_batch=1))
    t1 = loop.submit({"lane": "a"})
    loop.tick(drain=True)
    with pytest.raises(RuntimeError):
        t1.result(timeout=1.0)
    t2 = loop.submit({"lane": "a"})
    loop.tick(drain=True)
    t2.result(timeout=1.0)
    assert histogram("serve.lane.toy.a[]").count == 1


def test_histograms_merge_across_lanes_for_service_view():
    loop = _toy_loop(batch=BatchPolicy(max_batch=4))
    for lane in ("a", "b"):
        for i in range(3):
            loop.submit({"lane": lane, "i": i, "served": False})
    loop.drain()
    merged = obs.LatencyHistogram()
    for name, h in obs.histograms(prefix="serve.lane.toy.").items():
        merged.merge(h)
    assert merged.count == 6
