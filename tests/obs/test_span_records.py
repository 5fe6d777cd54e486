"""Span records: ids, parents and calls, kept only while profiling is on."""

import threading

import jax.numpy as jnp
import numpy as np
import pytest

import repro.xfft as xfft
from repro import obs
from repro.obs import record
from repro.plan import PlanCache
from repro.plan.api import plan_fft, resolve_call


@pytest.fixture(autouse=True)
def _fresh():
    obs.reset_counters()
    obs.reset_spans()
    yield
    obs.reset_spans()
    obs.reset_counters()


def _by_name(records):
    out = {}
    for r in records:
        out.setdefault(r.name, []).append(r)
    return out


def test_span_records_nest_ids_parents_and_calls():
    with obs.capture(profile=True):
        with obs.span("outer", a=1) as out:
            with obs.span("inner.one"):
                with obs.span("inner.leaf"):
                    pass
            with obs.span("inner.two"):
                pass
            out["b"] = 2
        with obs.span("second.root"):
            pass
    recs = obs.spans()
    # records arrive as spans close
    assert [r.name for r in recs] == ["inner.leaf", "inner.one", "inner.two", "outer",
                                      "second.root"]
    got = {r.name: r for r in recs}
    outer, one, leaf, two, root2 = (got[n] for n in
                                    ("outer", "inner.one", "inner.leaf", "inner.two",
                                     "second.root"))
    assert len({r.span_id for r in recs}) == 5
    assert outer.parent_id is None and outer.call_id == outer.span_id
    assert one.parent_id == outer.span_id and two.parent_id == outer.span_id
    assert leaf.parent_id == one.span_id
    assert {one.call_id, leaf.call_id, two.call_id} == {outer.span_id}
    assert root2.parent_id is None and root2.call_id == root2.span_id
    assert outer.start_ns <= one.start_ns <= leaf.start_ns <= leaf.end_ns <= one.end_ns
    assert one.end_ns <= two.start_ns <= two.end_ns <= outer.end_ns <= root2.start_ns
    assert outer.fields == {"a": 1, "b": 2}
    assert outer.duration_us == (outer.end_ns - outer.start_ns) / 1e3
    assert all(r.tid == threading.get_ident() for r in recs)


def test_records_only_while_profiling():
    with obs.capture() as trace:                   # events, no profiling
        with obs.span("unprofiled"):
            pass
    with obs.span("no.scope"):                     # sinks only (flight recorder)
        pass
    assert trace.select("unprofiled") and obs.spans() == []
    with xfft.config(observe=True):
        with obs.span("profiled"):
            pass
    with obs.span("after"):
        pass
    assert [r.name for r in obs.spans()] == ["profiled"]


def test_no_id_is_spent_while_profiling_is_off():
    with obs.capture(profile=True):
        with obs.span("first"):
            pass
    for _ in range(3):
        with obs.span("dark"):
            pass
    with obs.capture(profile=True):
        with obs.span("next"):
            pass
    first, nxt = obs.spans()
    assert nxt.span_id == first.span_id + 1


def test_buffer_is_bounded_and_counts_drops(monkeypatch):
    monkeypatch.setattr(record, "SPAN_CAPACITY", 3)
    with obs.capture(profile=True):
        for i in range(5):
            with obs.span("s", i=i):
                pass
    assert [r.fields["i"] for r in obs.spans()] == [0, 1, 2]
    assert obs.counters()["obs.spans.dropped"] == 2
    obs.reset_spans()
    assert obs.spans() == []


def test_annotation_carries_span_and_call_ids(monkeypatch):
    seen = []

    class Annotation:
        def __init__(self, name, **metadata):
            seen.append((name, metadata))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(record, "_annotation",
                        lambda name, **md: Annotation(name, **md))
    with obs.capture(profile=True):
        with obs.span("outer"):
            with obs.span("inner"):
                pass
    outer = next(r for r in obs.spans() if r.name == "outer")
    inner = next(r for r in obs.spans() if r.name == "inner")
    assert seen == [
        ("outer", {"span_id": outer.span_id, "call_id": outer.span_id}),
        ("inner", {"span_id": inner.span_id, "call_id": outer.span_id}),
    ]


def test_a_thread_starts_calls_of_its_own():
    """A thread starts with a fresh context: its spans are roots, never
    children of the span open in the thread that started it."""
    def worker():
        with obs.capture(profile=True):
            with obs.span("thread.root"):
                pass

    with obs.capture(profile=True):
        with obs.span("main.root"):
            th = threading.Thread(target=worker)
            th.start()
            th.join()
    got = {r.name: r for r in obs.spans()}
    assert got["thread.root"].parent_id is None
    assert got["thread.root"].call_id == got["thread.root"].span_id
    assert got["thread.root"].tid != got["main.root"].tid


# ----------------------------- the call path ------------------------------


def _descends(rec, ancestor, by_id):
    parent = by_id.get(rec.parent_id)
    while parent is not None:
        if parent.span_id == ancestor.span_id:
            return True
        parent = by_id.get(parent.parent_id)
    return False


def test_front_door_call_is_the_root_of_its_spans():
    x = jnp.asarray(np.random.default_rng(0).standard_normal((2, 16, 16)), jnp.float32)
    with xfft.config(observe=True, variant="stockham"):
        xfft.rfft2(x)
    recs = obs.spans()
    by_id = {r.span_id: r for r in recs}
    names = _by_name(recs)
    (call,) = names["xfft.call"]
    assert call.parent_id is None and call.call_id == call.span_id
    assert call.fields == {"kind": "rfft2", "shape": (2, 16, 16), "dtype": "float32"}
    assert all(r.call_id == call.span_id for r in recs)
    (resolve,) = names["plan.resolve"]
    (apply,) = names["engine.apply"]
    assert resolve.parent_id == call.span_id and apply.parent_id == call.span_id
    # the jnp engine's row and column passes, dispatched inside the engine
    for stage in ("fft.rows", "fft.columns"):
        (rec,) = names[stage]
        assert _descends(rec, apply, by_id)
    assert names["fft.rows"][0].end_ns <= names["fft.columns"][0].start_ns


def test_fused_kernel_launch_and_assembly_are_spans():
    """The fused call is one program: one ``kernel.launch`` span under
    ``engine.apply``, and no ``kernel.assemble`` after it (the complex
    result is built inside the kernel's program)."""
    x = jnp.asarray(np.random.default_rng(1).standard_normal((2, 16, 16)), jnp.float32)
    with xfft.config(observe=True, variant="fused"):
        y = xfft.rfft2(x)
    np.testing.assert_allclose(np.asarray(y), np.fft.rfft2(np.asarray(x)), atol=1e-3)
    names = _by_name(obs.spans())
    by_id = {r.span_id: r for r in obs.spans()}
    (apply,) = names["engine.apply"]
    (launch,) = names["kernel.launch"]
    assert launch.fields == {"kernel": "repro_rfft2_fused"}
    assert _descends(launch, apply, by_id)
    assert "kernel.assemble" not in names


def test_nested_front_door_calls_share_the_outer_call():
    x = jnp.ones((4, 4, 4), jnp.complex64)
    with xfft.config(observe=True):
        xfft.fftn(x)
    calls = _by_name(obs.spans())["xfft.call"]
    roots = [c for c in calls if c.parent_id is None]
    assert len(roots) == 1 and roots[0].fields["kind"] == "fftn"
    inner = [c for c in calls if c.parent_id is not None]
    assert [c.fields["kind"] for c in inner] == ["fft"] * 3
    assert all(c.parent_id == roots[0].span_id == c.call_id for c in inner)


def test_plan_resolve_is_a_timed_span_with_its_fields():
    fields = {"entry", "kind", "shape", "dtype", "direction", "n_devices", "layout",
              "precision", "backend", "mode", "outcome", "variant", "plan_mode",
              "est_time_s", "measured_us", "degrade_reason", "cache_path", "key"}
    cache = PlanCache()
    with obs.capture(profile=True) as trace:
        resolve_call("fft2d", (4, 32, 32), cache=cache)
        resolve_call("fft2d", (4, 32, 32), cache=cache)
        plan_fft("fft1d", (8, 64), cache=cache)
    events = trace.select("plan.resolve")
    assert [e["entry"] for e in events] == ["resolve_call", "resolve_call", "plan_fft"]
    assert [e["outcome"] for e in events] == ["miss", "hit", "miss"]
    for e in events:
        assert set(e.fields) == fields | {"duration_us"}
        assert e["duration_us"] >= 0
    recs = [r for r in obs.spans() if r.name == "plan.resolve"]
    assert len(recs) == 3 and all(r.parent_id is None for r in recs)
    assert [r.fields["outcome"] for r in recs] == ["miss", "hit", "miss"]
    assert obs.counters()["plan.resolve.hit"] == 1
