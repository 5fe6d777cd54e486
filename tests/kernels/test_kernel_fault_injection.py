"""Injected VMEM exhaustion must drive the REAL unfused failover path.

The fused engines consult the ``kernel.fused`` fault seam alongside the
kernels' genuine VMEM census, so a chaos run exercises the row /
corner-turn / column failover on frames that would normally fit — same
code path a too-big frame takes, no giant allocation needed.
"""

import jax.numpy as jnp
import numpy as np

import repro.xfft as xfft
from repro import obs
from repro.engines import get_engine
from repro.resilience import FaultPlan, FaultSpec


def fft2_kernel(x):
    """The ``fused`` engine's fft2d op: the fused kernel behind the seam."""
    return get_engine("fused").op("fft2d")(jnp.asarray(x))


def rfft2_kernel(x):
    """The ``fused`` engine's rfft2d op."""
    return get_engine("fused").op("rfft2d")(jnp.asarray(x))


def _frame(rng, shape=(16, 16)):
    return (
        rng.standard_normal(shape) + 1j * rng.standard_normal(shape)
    ).astype(np.complex64)


def test_small_frame_stays_fused(rng):
    with obs.capture() as trace:
        y = fft2_kernel(_frame(rng))
    assert trace.select("kernel.failover") == []
    assert np.asarray(y).shape == (16, 16)


def test_injected_vmem_exhaustion_forces_unfused_failover(rng):
    x = _frame(rng)
    plan = FaultPlan(
        FaultSpec("kernel.fused", mode="vmem", match={"kind": "fft2d"}, times=1)
    )
    with obs.capture() as trace, xfft.config(faults=plan):
        y = fft2_kernel(x)
    np.testing.assert_allclose(
        np.asarray(y), np.fft.fft2(x), rtol=1e-3, atol=1e-3
    )
    (inj,) = trace.select("resilience.fault")
    assert inj["seam"] == "kernel.fused" and inj["mode"] == "vmem"
    (fo,) = trace.select("kernel.failover")
    assert fo["kind"] == "fft2d"
    assert tuple(fo["shape"]) == (16, 16)


def test_rfft2_vmem_injection_fails_over(rng):
    x = rng.standard_normal((16, 16)).astype(np.float32)
    plan = FaultPlan(
        FaultSpec("kernel.fused", mode="vmem", match={"kind": "rfft2d"}, times=1)
    )
    with obs.capture() as trace, xfft.config(faults=plan):
        y = rfft2_kernel(x)
    np.testing.assert_allclose(
        np.asarray(y), np.fft.rfft2(x), rtol=1e-3, atol=1e-3
    )
    (fo,) = trace.select("kernel.failover")
    assert fo["kind"] == "rfft2d"


def test_vmem_budget_spent_next_call_fuses(rng):
    """times=1: the second trace takes the fused path again."""
    x = _frame(rng)
    plan = FaultPlan(FaultSpec("kernel.fused", mode="vmem", times=1))
    with obs.capture() as trace, xfft.config(faults=plan):
        fft2_kernel(x)
        fft2_kernel(x)
    assert len(trace.select("kernel.failover")) == 1
