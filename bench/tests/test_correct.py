"""The comparison that decides ``correct``, shown to pass and to fail.

Runs whole benchmark runs on the CPU at small sizes, skipping only the
harness's look for a chip: the program as it is reads correct; the control
(the reference at bfloat16 in the program's place) and each planted fault
read not correct.

    PYTHONPATH=src python -m pytest bench/tests -q
"""

from __future__ import annotations

import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import control, harness  # noqa: E402
from bench.reference import fft as ref_fft  # noqa: E402

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    """CPU runs here keep nothing in the checkout's compile cache."""
    import jax

    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", True)


def small(cell: str, **over) -> dict:
    spec = harness.load_spec()
    cfg = harness.load_config(spec, harness.find_cell(spec, cell)["config"])
    base = {"frame": [64, 64], "frames_per_call": 6, "check_frames": 3}
    return {**cfg, **{k: v for k, v in base.items() if k in cfg}, **over}


def run(cell: str, override=None, seconds: float = 0.5, seed: int = 2**31 + 77,
        **over) -> dict:
    """One run at a small size on the CPU."""
    return harness.run_cell(cell, seed, seconds, False, config=small(cell, **over),
                            require_tpu=False, override=override)


# ------------------------------ frames cells ------------------------------


def rfft2_alter_bin(x):
    import repro.xfft as xfft

    y = xfft.rfft2(x)
    scale = np.abs(y).max()
    return y.at[:, 1, 1].add(1e-3 * scale)


def rfft2_half_batch(x):
    import repro.xfft as xfft

    half = x.shape[0] // 2
    y = xfft.rfft2(x[:half])
    return np.concatenate([np.asarray(y), np.zeros_like(y)])


@pytest.mark.parametrize("cell", ["emccd512_stack_batch", "falcon4_movie_batch"])
def test_program_is_correct(cell):
    res = run(cell)
    assert res["correct"], res["checks"]
    assert res["checks"]["max_err"]["value"] < 1e-5
    assert res["checks"]["bin_err"]["value"] < 1e-5


@pytest.mark.parametrize("cell", ["emccd512_stack_batch", "falcon4_movie_batch"])
@pytest.mark.parametrize("fault", [control.rfft2_control, rfft2_alter_bin, rfft2_half_batch],
                         ids=["control_bfloat16", "answer_altered", "half_batch_left_out"])
def test_frames_fault_is_caught(cell, fault):
    res = run(cell, override=fault)
    assert not res["correct"], res["checks"]


def rfft2_half_batch_repeated(x):
    """Half the frames transformed, their spectra standing in for the rest."""
    import repro.xfft as xfft

    y = np.asarray(xfft.rfft2(x[: x.shape[0] // 2]))
    return np.concatenate([y, y])


@pytest.mark.parametrize("seed", [2**31 + 5, 2**33 + 1, 7])
@pytest.mark.parametrize("fault", [rfft2_half_batch, rfft2_half_batch_repeated],
                         ids=["zeros", "repeated"])
def test_half_batch_fails_the_bins_of_every_frame(seed, fault):
    """Half of the Falcon cell's 20 frames left out reads not correct on any
    seed, whichever 3 frames the whole-frame comparison samples."""
    res = run("falcon4_movie_batch", override=fault, seed=seed, frames_per_call=20)
    bins = res["checks"]["bin_err"]
    assert not res["correct"] and bins["value"] > bins["limit"], res["checks"]


def test_reference_bins_match_the_reference_transform():
    x = np.random.default_rng(3).standard_normal((5, 32, 64)).astype(np.float32)
    rows, cols = np.array([0, 3, 31]), np.array([0, 1, 17, 32])
    bins, rms = ref_fft.rfft2_bins(x, rows, cols, chunk=2)
    full = ref_fft.rfft2(x)
    np.testing.assert_allclose(bins, full[:, rows][:, :, cols], rtol=0, atol=1e-10)
    energy = np.sum(np.abs(np.fft.fft2(x.astype(np.float64))) ** 2, axis=(1, 2))
    np.testing.assert_allclose(rms, np.sqrt(energy / (32 * 64)), rtol=1e-12)


# --------------------------- failures and failovers ---------------------------


def rfft2_emitting(name):
    def call(x):
        import repro.xfft as xfft
        from repro import obs

        obs.emit(name, engine="planted")
        return xfft.rfft2(x)

    return call


def test_failover_is_counted_not_failed():
    """A call that the ladder fails over is served: the window counts it
    under ``failover_calls``, and none of its frames under ``failed``."""
    res = run("emccd512_stack_batch", override=rfft2_emitting("resilience.failover"))
    assert res["failed"] == 0 and res["failover_calls"] == res["calls"] > 0
    assert res["program_events_outside_window"] == 2  # the two warm-up calls


def test_lane_error_fails_its_frames():
    res = run("emccd512_stack_batch", override=rfft2_emitting("serve.lane.error"))
    assert res["failed"] == res["attempted"] > 0 and res["failover_calls"] == 0


@pytest.mark.parametrize("ahead", [0, 5])
def test_every_call_sent_is_counted_and_checked(ahead, monkeypatch):
    """Whatever the number of calls sent ahead, the window waits for every
    call it sent, counts each, and checks the last one."""
    traffic = harness.load_traffic("pipelined_rfft2")
    monkeypatch.setattr(harness, "load_traffic", lambda name: {**traffic, "calls_ahead": ahead})
    res = run("emccd512_stack_batch")
    assert res["correct"] and res["attempted"] == res["calls"] * 6 > 0, res
    res = run("emccd512_stack_batch", override=rfft2_alter_bin)
    assert not res["correct"], res["checks"]
