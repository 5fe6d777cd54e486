"""The sharded cell's comparison and its all-to-all reader, on the CPU.

Whole runs of ``grid32k_pencil4`` at a small copy of its configuration
(256², four fake CPU devices, in a subprocess so that this process keeps
one device): the program reads correct; its bfloat16 control and each
planted fault (one chip's quarter of the output zeroed, two chips' column
blocks swapped) read not correct; a program that hands back the grid
gathered is refused before the first call. ``alltoall_share.frames`` is
read from a synthetic four-device trace reduction.

    PYTHONPATH=src python -m pytest bench/tests -q
"""

from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys
import types

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, trace_reduce  # noqa: E402
from bench.reference import ifft2_lines  # noqa: E402

_SCRIPT = r"""
import os, sys, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path[:0] = [".", "src"]
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
jax.config.update("jax_enable_compilation_cache", False)
import repro.xfft as xfft
from bench import harness
from bench.ops import ifft2_sharded as op

spec = harness.load_spec()
cfg = dict(harness.load_config(spec, "radio_wstack_32k"), frame=[256, 256])


def rebuilt(y, data):
    shards = sorted(y.addressable_shards, key=lambda s: s.device.id)
    arrays = [jax.device_put(d, s.device) for s, d in zip(shards, data(shards))]
    return jax.make_array_from_single_device_arrays(y.shape, y.sharding, arrays)


def quarter_zeroed(x):
    return rebuilt(xfft.ifft2(x), lambda sh: [jnp.zeros_like(s.data) if i == 2 else s.data
                                              for i, s in enumerate(sh)])


def column_blocks_swapped(x):
    return rebuilt(xfft.ifft2(x), lambda sh: [sh[1].data, sh[0].data] + [s.data for s in sh[2:]])


def gathered(x):
    y = xfft.ifft2(x)
    return jax.device_put(y, NamedSharding(x.sharding.mesh, P()))


out = {}
for name, override in [("program", None), ("control", op.control),
                       ("quarter_zeroed", quarter_zeroed),
                       ("column_blocks_swapped", column_blocks_swapped),
                       ("gathered", gathered)]:
    try:
        res = harness.run_cell("grid32k_pencil4", 2**31 + 15, 0.3, False, spec=spec,
                               config=cfg, require_tpu=False, override=override)
        out[name] = {"correct": res["correct"], "checks": res["checks"],
                     "attempted": res["attempted"], "calls": res["calls"],
                     "metrics": sorted(res["metrics"])}
    except RuntimeError as e:
        out[name] = {"refused": str(e)}
print("RESULTS " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    run = subprocess.run([sys.executable, "-c", _SCRIPT], capture_output=True, text=True,
                         cwd=ROOT, env=env, timeout=900)
    assert run.returncode == 0, run.stderr[-4000:]
    (line,) = [ln for ln in run.stdout.splitlines() if ln.startswith("RESULTS ")]
    return json.loads(line[len("RESULTS "):])


def test_program_is_correct(runs):
    r = runs["program"]
    assert r["correct"], r["checks"]
    assert r["checks"]["max_err"]["value"] < 1e-5 and r["checks"]["bin_err"]["value"] < 1e-5
    assert r["attempted"] == r["calls"] > 0
    assert r["metrics"] == ["frames_per_s", "setup_s"]


@pytest.mark.parametrize("fault", ["control", "quarter_zeroed", "column_blocks_swapped"])
def test_fault_is_caught(runs, fault):
    r = runs[fault]
    assert not r["correct"], r["checks"]
    assert r["checks"]["max_err"]["value"] > r["checks"]["max_err"]["limit"]


def test_gathering_program_is_refused_before_the_first_call(runs):
    assert "not split evenly over the chips" in runs["gathered"]["refused"]


def test_reference_lines_match_numpy():
    rng = np.random.default_rng(15)
    x = (rng.standard_normal((64, 128)) + 1j * rng.standard_normal((64, 128))).astype(
        np.complex64)
    rows, cols = np.array([0, 5, 63]), np.array([1, 64, 127])
    got_rows, got_cols, norm = ifft2_lines.ifft2_lines(
        [(32, x[32:]), (0, x[:32])], 64, 128, rows, cols, step=10)
    ref = np.fft.ifft2(x.astype(np.complex128))
    np.testing.assert_allclose(got_rows, ref[rows], rtol=0, atol=1e-15)
    np.testing.assert_allclose(got_cols, ref[:, cols], rtol=0, atol=1e-15)
    # Parseval: the rms of the inverse transform is the norm over H*W
    assert np.sqrt(np.mean(np.abs(ref) ** 2)) == pytest.approx(norm / (64 * 128), rel=1e-12)


def _reader():
    return harness.load_metric_reader("alltoall_share.frames")


def test_alltoall_share_reads_a_four_device_trace():
    # per chip: 100 ns window, ops over [0, 80]; all-to-all 10 + 6 ns (the
    # instruction JAX names all_to_all, and a split start/done pair)
    ops = {d: [("jit_repro_pencil_ifft2/fusion", 0, 40),
               ("jit_repro_pencil_ifft2/all_to_all", 40, 50),
               ("jit_repro_pencil_ifft2/all-to-all-start", 50, 52),
               ("jit_repro_pencil_ifft2/all-to-all-done", 52, 56),
               ("jit_repro_pencil_ifft2/fusion", 56, 80)] for d in range(4)}
    ops[3] = [(n, s, e + 20 if "fusion" in n and s else e) for n, s, e in ops[3]]
    red = trace_reduce.reduce_events(ops, [("bench.window", 0, 100)], (0, 100))
    assert red.n_devices == 4
    value = _reader()(types.SimpleNamespace(reduced=red))
    busy = (80 + 80 + 80 + 100) / 4
    assert value == pytest.approx(100.0 * 16 / busy)


def test_alltoall_share_is_silent_without_all_to_all():
    ops = {d: [("jit_repro_jnp_fft_pass/fusion", 0, 50)] for d in range(4)}
    red = trace_reduce.reduce_events(ops, [("bench.window", 0, 100)], (0, 100))
    assert _reader()(types.SimpleNamespace(reduced=red)) is None
    assert _reader()(types.SimpleNamespace(reduced=None)) is None
