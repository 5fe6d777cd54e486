"""Sharded input at the front door: ``xfft.fft2``/``ifft2`` on a grid split
over four devices plan an ``fft2d_pencil`` problem and run a pencil engine
through the ladder, nothing gathered.

The four-device cases run in one subprocess with four fake CPU devices (so
the rest of the suite keeps seeing one device) and report one record per
case; the tests below read them. The planner's HBM gate is checked here in
process, on keys built for a described chip.
"""

import json
import os
import subprocess
import sys

import pytest

from repro.plan.autotune import variant_candidates
from repro.plan.plan import ProblemKey

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

import repro.xfft as xfft
import repro.xfft._transforms as front
from repro import obs
from repro.launch.mesh import make_mesh
from repro.plan import execute, plan_fft, problem_key, resolve_call
from repro.resilience import FaultPlan, FaultSpec, reset

mesh = make_mesh((4,), ("data",))
rng = np.random.default_rng(15)
SHAPES = {"64x64": (64, 64), "128x256": (128, 256), "b2x64x64": (2, 64, 64)}
out = {}

# Every plan the front door runs goes through the ladder: record each one.
ran = []
real_run_plan = front._run_plan
def spy(plan, runner):
    ran.append((plan.key.kind, plan.key.n_devices, plan.key.layout))
    return real_run_plan(plan, runner)
front._run_plan = spy


def data(shape):
    return (rng.standard_normal(shape) + 1j * rng.standard_normal(shape)).astype(np.complex64)


def place(x, layout):
    lead = (None,) * (x.ndim - 2)
    spec = P(*lead, "data", None) if layout == "rows" else P(*lead, None, "data")
    return jax.device_put(x, NamedSharding(mesh, spec))


def shards(a):
    return sorted((str(s.device), list(s.data.shape)) for s in a.addressable_shards)


def err(got, ref):
    return float(np.max(np.abs(np.asarray(got) - ref)) / np.max(np.abs(ref)))


for sname, shape in SHAPES.items():
    x = data(shape)
    for layout in ("rows", "cols"):
        xs = place(x, layout)
        for fn in ("fft2", "ifft2"):
            ref_fn = getattr(np.fft, fn)
            for norm in ("backward", "ortho", "forward"):
                del ran[:]
                with obs.capture() as trace:
                    y = getattr(xfft, fn)(xs, norm=norm)
                resolved = trace.select("plan.resolve")
                out[f"{fn}-{layout}-{sname}-{norm}"] = {
                    "err": err(y, ref_fn(x.astype(np.complex128), norm=norm)),
                    "ran": ran[:],
                    "plans": [(e["kind"], e["n_devices"], e["layout"]) for e in resolved],
                    "in_shards": shards(xs), "out_shards": shards(y),
                    "out_spec": [s for s in y.sharding.spec],
                    "applied": [(e["engine"], e["kind"], e["n_devices"])
                                for e in trace.select("engine.apply")],
                    "dispatch": [{k: e[k] for k in ("n_devices", "axis", "layout_in",
                                                     "layout_out", "chunks", "variant")}
                                 for e in trace.select("pencil.dispatch")],
                }
        back = xfft.ifft2(xfft.fft2(xs))
        out[f"roundtrip-{layout}-{sname}"] = {
            "err": err(back, x), "same_layout": back.sharding.is_equivalent_to(xs.sharding, xs.ndim),
            "out_shards": shards(back),
        }

# fftn/ifftn over two axes take the same path.
x = data((64, 64))
xs = place(x, "rows")
del ran[:]
out["fftn"] = {"err": err(xfft.fftn(xs), np.fft.fftn(x.astype(np.complex128))),
               "ran": ran[:]}
del ran[:]
out["ifftn"] = {"err": err(xfft.ifftn(xs), np.fft.ifftn(x.astype(np.complex128))),
                "ran": ran[:]}

# A sharded call is counted.
before = obs.counters().get("xfft.sharded_calls", 0)
xfft.fft2(xs)
out["counted"] = obs.counters().get("xfft.sharded_calls", 0) - before

# A fault on the planned pencil rung fails over to another pencil rung.
planned = resolve_call("fft2d_pencil", (64, 64), n_devices=4, layout="rows").variant
reset()
faults = FaultPlan(FaultSpec("engine.apply", mode="error", match={"engine": planned}, times=1))
with obs.capture() as trace, xfft.config(faults=faults):
    y = xfft.fft2(xs)
out["failover"] = {
    "planned": planned,
    "err": err(y, np.fft.fft2(x.astype(np.complex128))),
    "out_shards": shards(y),
    "failovers": [(e["engine"], e["kind"], e["next"]) for e in trace.select("resilience.failover")],
    "applied": [(e["engine"], e["kind"], e["n_devices"]) for e in trace.select("engine.apply")],
}
reset()

# A single-device call resolves as before: same key, same engine.
del ran[:]
with obs.capture() as trace:
    y = xfft.fft2(jnp.asarray(x))
ev = trace.select("plan.resolve")[0]
out["single"] = {"err": err(y, np.fft.fft2(x.astype(np.complex128))), "ran": ran[:],
                 "key": ev["key"], "variant": ev["variant"],
                 "planned": resolve_call("fft2d", (64, 64)).variant,
                 "expected_key": problem_key("fft2d", (64, 64)).cache_key()}

# A pin or backend scope that leaves no pencil engine is refused by name;
# a pinned pencil engine serves the sharded grid.
out["pinned"] = {}
for label, scope in (("variant-fused", {"variant": "fused"}),
                     ("variant-fused_r4", {"variant": "fused_r4"}),
                     ("backend-pallas", {"backend": "pallas"}),
                     ("variant-stockham", {"variant": "stockham"})):
    del ran[:]
    try:
        with xfft.config(**scope):
            y = xfft.ifft2(xs)
        out["pinned"][label] = {"err": err(y, np.fft.ifft2(x.astype(np.complex128))),
                                "ran": ran[:], "out_shards": shards(y)}
    except ValueError as e:
        out["pinned"][label] = {"error": str(e), "ran": ran[:]}

# plan.execute places the grid on the mesh in the plan's layout and runs
# the same engine.
plan = plan_fft("fft2d_pencil", (64, 64), n_devices=4, direction="inv", layout="cols")
y = execute(plan, x, mesh=mesh)
out["execute"] = {"err": err(y, np.fft.ifft2(x.astype(np.complex128))),
                  "out_spec": [s for s in y.sharding.spec], "out_shards": shards(y)}
print("RESULTS " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def results():
    env = dict(os.environ, PYTHONPATH="src")
    run = subprocess.run(
        [sys.executable, "-c", _SCRIPT], capture_output=True, text=True,
        cwd=os.path.join(os.path.dirname(__file__), "..", ".."), env=env, timeout=900,
    )
    assert run.returncode == 0, run.stderr[-4000:]
    (line,) = [ln for ln in run.stdout.splitlines() if ln.startswith("RESULTS ")]
    return json.loads(line[len("RESULTS "):])


SHAPES = {"64x64": (64, 64), "128x256": (128, 256), "b2x64x64": (2, 64, 64)}
LAYOUTS = ("rows", "cols")
OTHER = {"rows": "cols", "cols": "rows"}


def _quarters(shape, layout):
    """Each device's shard of a (..., H, W) grid split in ``layout``."""
    *lead, h, w = shape
    return [*lead, h // 4, w] if layout == "rows" else [*lead, h, w // 4]


@pytest.mark.parametrize("norm", ["backward", "ortho", "forward"])
@pytest.mark.parametrize("sname", list(SHAPES))
@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("fn", ["fft2", "ifft2"])
def test_sharded_transform_matches_numpy(results, fn, layout, sname, norm):
    r = results[f"{fn}-{layout}-{sname}-{norm}"]
    assert r["err"] < 1e-5, r["err"]
    # planned as a pencil problem over four devices, run through the ladder
    assert r["plans"] == [["fft2d_pencil", 4, layout]]
    assert r["ran"] == [["fft2d_pencil", 4, layout]]
    (applied,) = r["applied"]
    assert applied[1:] == ["fft2d_pencil", 4]
    (dispatch,) = r["dispatch"]
    assert dispatch["n_devices"] == 4 and dispatch["axis"] == "data"
    assert (dispatch["layout_in"], dispatch["layout_out"]) == (layout, OTHER[layout])
    assert dispatch["variant"] == applied[0] and dispatch["chunks"] >= 1


@pytest.mark.parametrize("sname", list(SHAPES))
@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("fn", ["fft2", "ifft2"])
def test_each_device_holds_a_quarter(results, fn, layout, sname):
    """Input and output are split evenly over the four devices, the output
    in the other layout: nothing is gathered."""
    r = results[f"{fn}-{layout}-{sname}-backward"]
    shape = SHAPES[sname]
    assert len({d for d, _ in r["in_shards"]}) == 4
    assert all(s == _quarters(shape, layout) for _, s in r["in_shards"])
    assert len({d for d, _ in r["out_shards"]}) == 4
    assert all(s == _quarters(shape, OTHER[layout]) for _, s in r["out_shards"])


@pytest.mark.parametrize("sname", list(SHAPES))
@pytest.mark.parametrize("layout", LAYOUTS)
def test_roundtrip_keeps_the_layout(results, layout, sname):
    r = results[f"roundtrip-{layout}-{sname}"]
    assert r["err"] < 1e-5 and r["same_layout"]
    assert all(s == _quarters(SHAPES[sname], layout) for _, s in r["out_shards"])


@pytest.mark.parametrize("fn", ["fftn", "ifftn"])
def test_fftn_over_two_sharded_axes_is_a_pencil(results, fn):
    r = results[fn]
    assert r["err"] < 1e-5 and r["ran"] == [["fft2d_pencil", 4, "rows"]]


def test_sharded_calls_are_counted(results):
    assert results["counted"] == 1


def test_pencil_fault_fails_over_to_another_pencil_rung(results):
    r = results["failover"]
    assert r["err"] < 1e-5
    ((failed, kind, nxt),) = r["failovers"]
    assert failed == r["planned"] and kind == "fft2d_pencil"
    assert nxt not in (None, r["planned"], "fused", "fused_r4")
    # the injected fault fires before the planned engine's dispatch span;
    # the rung that served ran the pencil kind across the four devices
    assert r["applied"] == [[nxt, "fft2d_pencil", 4]]
    assert all(s == [64, 16] for _, s in r["out_shards"])


def test_single_device_call_resolves_as_before(results):
    r = results["single"]
    assert r["err"] < 1e-5 and r["ran"] == [["fft2d", 1, ""]]
    assert r["key"] == r["expected_key"] == "v5|fft2d|fwd|cpu|cpu|64x64|complex64|d1|ax-2,-1|single|be*"
    assert r["variant"] == r["planned"]


@pytest.mark.parametrize("label,scope", [("variant-fused", "variant='fused'"),
                                         ("variant-fused_r4", "variant='fused_r4'"),
                                         ("backend-pallas", "backend=('pallas',)")])
def test_pinned_single_device_engine_is_refused_on_a_sharded_grid(results, label, scope):
    """A scope that leaves no engine for the sharded grid fails before any
    engine runs, naming the scope and the engines that would serve it."""
    r = results["pinned"][label]
    assert r["ran"] == []
    assert f"xfft.config({scope}) leaves no engine" in r["error"]
    assert "sharded over 4 devices" in r["error"]
    assert "('looped', 'unrolled', 'stockham', 'radix4')" in r["error"]


def test_pinned_pencil_engine_serves_a_sharded_grid(results):
    r = results["pinned"]["variant-stockham"]
    assert r["err"] < 1e-5 and r["ran"] == [["fft2d_pencil", 4, "rows"]]
    assert all(s == [64, 16] for _, s in r["out_shards"])


def test_execute_runs_a_pencil_plan_on_a_mesh(results):
    r = results["execute"]
    assert r["err"] < 1e-5 and r["out_spec"] == ["data"]
    assert all(s == [16, 64] for _, s in r["out_shards"])


def _tpu_key(shape, n_devices, kind="fft2d_pencil", layout=""):
    return ProblemKey(kind=kind, backend="tpu", device_kind="TPU v5 lite", shape=shape,
                      dtype="complex64", n_devices=n_devices, layout=layout)


@pytest.mark.parametrize("layout", LAYOUTS)
def test_pencil_engines_serve_a_grid_that_fits_the_chips(layout):
    cands = variant_candidates(_tpu_key((32768, 32768), 4, layout=layout))
    assert set(cands) == {"looped", "unrolled", "stockham", "radix4"}


@pytest.mark.parametrize("shape,n_devices", [((65536, 65536), 4), ((32768, 32768), 2),
                                             ((64, 64), 1)])
def test_pencil_plans_are_declined_past_hbm_or_on_one_device(shape, n_devices):
    """Past the chips' HBM (the pencil program's working set, 4.6 blocks a
    chip) or on one device, no engine serves the pencil kind."""
    with pytest.raises(ValueError, match="no registered engine supports kind 'fft2d_pencil'"):
        variant_candidates(_tpu_key(shape, n_devices))


def test_single_device_kinds_take_no_layout():
    with pytest.raises(ValueError, match="takes no layout"):
        _tpu_key((64, 64), 1, kind="fft2d", layout="rows")
    assert _tpu_key((64, 64), 4).layout == "rows"
    with pytest.raises(ValueError, match="unknown pencil layout"):
        _tpu_key((64, 64), 4, layout="diagonal")
