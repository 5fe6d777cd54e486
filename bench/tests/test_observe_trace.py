"""The program's own spans in a chip trace, and the readers that use them.

``data/emccd512_stack_batch_observe_1s.xplane.pb`` is the profiler trace of
a one-second traced run of ``emccd512_stack_batch`` on one TPU v5e, taken
with ``xfft.config(observe=True)`` around the window, as every traced run
takes it: the program's spans (``xfft.call``, ``plan.resolve``,
``engine.apply``, ``kernel.launch``, ``kernel.assemble``) carry their
``span_id`` and ``call_id``, and the fused kernel is named
``repro_rfft2_fused``.

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

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")

TRACE = pathlib.Path(__file__).parent / "data" / "emccd512_stack_batch_observe_1s.xplane.pb"
CALL_SPANS = ("xfft.call", "plan.resolve", "engine.apply", "kernel.launch",
              "kernel.assemble")


@pytest.fixture(scope="module")
def events():
    return trace_reduce.read_xplane(TRACE, "bench.window")


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce_file(TRACE, n_devices=1, window_name="bench.window")


@pytest.fixture(scope="module")
def host_stats():
    """(name, start_ns, stats) of every host event with span ids."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(str(TRACE)).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    stats = dict(e.stats)
                    if "span_id" in stats:
                        out.append((e.name, int(e.start_ns), stats))
    return out


def test_span_names_stay_bare_with_their_ids(events, reduced, host_stats):
    _, spans, (lo, hi) = events
    names = {n for n, _, _ in spans}
    assert set(CALL_SPANS) <= names
    assert not any("#" in n or "span_id" in n for n in names)
    # every idle gap is named after a bare span, as trace_reduce matches them
    assert reduced.gap_s
    assert all(trace_reduce.SPAN_NAME.match(g) or g == trace_reduce.NO_SPAN
               for g in reduced.gap_s)
    assert {n for n, _, _ in host_stats} == set(CALL_SPANS)
    roots = {s["span_id"] for n, _, s in host_stats if n == "xfft.call"}
    assert all(s["call_id"] == s["span_id"] for n, _, s in host_stats if n == "xfft.call")
    assert all(s["call_id"] in roots for _, _, s in host_stats)
    # one front-door call per call the harness sent in the window
    calls = sum(1 for n, s, e in spans if n == "bench.call" and lo <= s and e <= hi)
    window_roots = sum(1 for n, t, _ in host_stats if n == "xfft.call" and lo <= t <= hi)
    assert calls == window_roots > 0


def test_pallas_kernel_share_reads_the_named_kernel(reduced):
    named = {n: s for n, s in reduced.op_s.items()
             if n.rsplit("/", 1)[-1].startswith("repro_")}
    assert set(named) == {"jit_rfft2_fused/repro_rfft2_fused"}
    ctx = types.SimpleNamespace(reduced=reduced)
    share = harness.load_metric_reader("pallas_kernel_share.frames")(ctx)
    assert share == pytest.approx(100 * sum(named.values()) / reduced.busy_s)
    assert 0 < share <= 100
