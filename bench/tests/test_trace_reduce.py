"""The trace reduction's arithmetic, on a trace recorded on the chip.

``data/emccd512_stack_batch_1s.xplane.pb`` is the profiler trace of a
one-second traced run of ``emccd512_stack_batch`` on one TPU v5e (ten
``xfft.rfft2`` calls on a 2048x512x512 float32 stack).

    PYTHONPATH=src python -m pytest bench/tests -q
"""

from __future__ import annotations

import pathlib
import sys
import types

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, trace_reduce  # noqa: E402
from bench.ops import rfft2  # noqa: E402

TRACE = pathlib.Path(__file__).parent / "data" / "emccd512_stack_batch_1s.xplane.pb"


@pytest.fixture(scope="module")
def events():
    return trace_reduce.read_xplane(TRACE, "bench.window")


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce_file(TRACE, n_devices=1, window_name="bench.window")


def test_union_merges_overlaps_and_clips_to_the_window():
    ops = {0: [("a", 0, 10), ("b", 5, 20), ("c", 30, 40), ("d", 95, 130)],
           1: [("a", 0, 200)]}
    spans = [("bench.window", 2, 100), ("bench.call", 2, 50), ("bench.block", 25, 50)]
    red = trace_reduce.reduce_events(ops, spans, (2, 100))
    # chip 0: [2, 20] + [30, 40] + [95, 100] = 33 ns; chip 1: the whole 98 ns
    assert red.busy_s == pytest.approx((33 + 98) / 2 / 1e9)
    assert red.window_s == pytest.approx(98e-9)
    assert red.idle_share == pytest.approx(1 - (33 + 98) / 2 / 98)
    assert red.op_s["a"] == pytest.approx((8 + 98) / 2 / 1e9)
    # chip 0's gaps: [20, 30] in bench.block, [40, 95] mostly outside any
    # call: named after the span open at its midpoint (67: bench.window)
    assert red.gap_s == pytest.approx({"bench.block": 5e-9, "bench.window": 27.5e-9})


def test_trace_busy_time_is_the_union_of_device_operations(events, reduced):
    device_ops, _, (lo, hi) = events
    (ops,) = device_ops.values()
    clipped = sorted((max(s, lo), min(e, hi)) for _, s, e in ops if e > lo and s < hi)
    # on one TPU core the operations do not overlap: the union is their sum
    assert all(a[1] <= b[0] for a, b in zip(clipped, clipped[1:]))
    busy = sum(e - s for s, e in clipped) / 1e9
    assert reduced.busy_s == pytest.approx(busy, rel=1e-12)
    assert reduced.window_s == pytest.approx((hi - lo) / 1e9, rel=1e-12)
    assert 0 < reduced.busy_s < reduced.window_s
    assert reduced.idle_share == pytest.approx(1 - busy / ((hi - lo) / 1e9))
    assert sum(reduced.op_s.values()) == pytest.approx(busy, rel=1e-9)
    assert sum(reduced.gap_s.values()) == pytest.approx(reduced.window_s - busy, rel=1e-9)


def test_trace_names_operations_by_program(reduced):
    top = reduced.breakdown()["device_ops"][0]
    assert top[0] == "jit_rfft2_fused/rfft2_fused"
    assert all("/" in name for name in reduced.op_s)


def test_roofline_share_arithmetic(events, reduced):
    _, spans, (lo, hi) = events
    calls = sum(1 for n, s, e in spans if n == "bench.call" and lo <= s and e <= hi)
    assert calls == 10
    work = calls * 2048 * rfft2.work_bytes(512, 512)
    assert work == calls * 2048 * (4 * 512 * 512 + 8 * 512 * 257)
    peaks = harness.load_json(harness.BENCH_DIR / "peaks.json")["TPU v5 lite"]
    ctx = types.SimpleNamespace(reduced=reduced, peaks=peaks,
                                window=types.SimpleNamespace(work_bytes_per_chip=work))
    share = harness.load_metric_reader("transform_roofline")(ctx)
    assert share == pytest.approx(100 * work / 819e9 / reduced.busy_s)
    assert 0 < share <= 100
    idle = harness.load_metric_reader("device_idle_share.frames")(ctx)
    assert idle == pytest.approx(100 * (1 - reduced.busy_s / reduced.window_s))
    untraced = types.SimpleNamespace(reduced=None, peaks=peaks, window=ctx.window)
    assert harness.load_metric_reader("transform_roofline")(untraced) is None
    assert harness.load_metric_reader("device_idle_share.frames")(untraced) is None
