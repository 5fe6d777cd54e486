"""The benchmark's readers of the program's own records
(``bench/metrics/*.py``), on fake span records, counters and traces."""

import pathlib
import sys
import types

import pytest

from repro import obs
from repro.obs import SpanRecord

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import harness, trace_reduce  # noqa: E402


def _reader(name):
    return harness.load_metric_reader(name)


def _rec(name, span_id, parent_id, call_id, start_us, end_us):
    return SpanRecord(name, span_id, parent_id, call_id, int(start_us * 1e3),
                      int(end_us * 1e3), 1, {})


def _ctx(calls=None, reduced=None):
    return types.SimpleNamespace(reduced=reduced,
                                 window=types.SimpleNamespace(notes={"calls": calls}))


def test_host_path_is_the_median_call_less_its_engine_spans(monkeypatch):
    records = [
        # call 1: 1000 us, engine.apply 800 us -> 200 us of host path
        _rec("plan.resolve", 2, 1, 1, 10, 60),
        _rec("kernel.launch", 4, 3, 1, 120, 130),
        _rec("engine.apply", 3, 1, 1, 100, 900),
        _rec("xfft.call", 1, None, 1, 0, 1000),
        # call 2: a failed engine.apply and a served one, both subtracted;
        # an engine.apply inside another is not subtracted twice
        _rec("engine.apply", 6, 5, 5, 2100, 2200),
        _rec("engine.apply", 8, 7, 5, 2350, 2400),
        _rec("engine.apply", 7, 5, 5, 2300, 2900),
        _rec("xfft.call", 5, None, 5, 2000, 3000),     # 1000 - 100 - 600 = 300
        # call 3 holds a nested front-door call: one call, one cost
        _rec("engine.apply", 11, 10, 9, 4100, 4500),
        _rec("xfft.call", 10, 9, 9, 4050, 4600),
        _rec("xfft.call", 9, None, 9, 4000, 5000),     # 1000 - 400 = 600
    ]
    monkeypatch.setattr(obs, "spans", lambda: records)
    assert _reader("host_path_us.frames")(_ctx(calls=3)) == pytest.approx(300.0)


def test_host_path_counts_calls_under_another_span(monkeypatch, capsys):
    """A front-door call inside a service's span is still one call."""
    records = [
        _rec("engine.apply", 3, 2, 1, 10, 20),
        _rec("xfft.call", 2, 1, 1, 0, 50),
        _rec("serve.batch", 1, None, 1, 0, 100),
    ]
    monkeypatch.setattr(obs, "spans", lambda: records)
    assert _reader("host_path_us.frames")(_ctx(calls=1)) == pytest.approx(40.0)
    assert "1 xfft.call records, 1 calls" in capsys.readouterr().err


def test_host_path_reads_nothing_without_records(monkeypatch):
    monkeypatch.setattr(obs, "spans", lambda: [])
    assert _reader("host_path_us.frames")(_ctx(calls=0)) is None
    monkeypatch.delattr(obs, "spans")               # a program without records
    assert _reader("host_path_us.frames")(_ctx(calls=4)) is None


def _reduction(op_s, busy_s=30.0):
    return trace_reduce.Reduction(window_s=31.0, busy_s=busy_s, op_s=op_s, gap_s={})


def test_pallas_kernel_share_reads_the_named_kernels():
    red = _reduction({
        "jit_rfft2_fused/repro_rfft2_fused": 14.0,
        "jit_rfft2_fused/copy": 2.0,
        "jit_add/custom-call:X64Combine": 3.0,
        "jit_f/not_repro_op": 1.0,
    })
    assert _reader("pallas_kernel_share.frames")(_ctx(reduced=red)) == pytest.approx(
        100 * 14.0 / 30.0)
    jnp_only = _reduction({"jit_add/add": 3.0, "jit_dynamic_slice/copy": 2.0})
    assert _reader("pallas_kernel_share.frames")(_ctx(reduced=jnp_only)) == 0.0


def test_pallas_kernel_share_reads_nothing_untraced(monkeypatch):
    assert _reader("pallas_kernel_share.frames")(_ctx()) is None
    red = _reduction({"jit_rfft2_fused/rfft2_fused": 14.0})
    monkeypatch.delattr(obs, "spans")               # kernels without stable names
    assert _reader("pallas_kernel_share.frames")(_ctx(reduced=red)) is None


def test_compile_seconds_sum_the_phase_counters(monkeypatch, capsys):
    counts = {"compile.tracing_us": 1_500_000, "compile.lowering_us": 2_000_000,
              "compile.backend_us": 6_500_000, "compile.cache.hit": 40,
              "compile.cache.miss": 2, "plan.resolve": 9}
    monkeypatch.setattr(obs, "counters", lambda: counts)
    assert _reader("compile_s.setup")(_ctx()) == pytest.approx(10.0)
    assert "cache hits 40, misses 2" in capsys.readouterr().err
    monkeypatch.setattr(obs, "counters", lambda: {"plan.resolve": 9})
    assert _reader("compile_s.setup")(_ctx()) is None
