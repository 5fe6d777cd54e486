"""Each fused entry point's fused branch is one compiled program.

The Pallas kernels take and give (re, im) float32 planes. Split or
assembled op by op around the kernel, a complex64 array costs extra device
programs (and, on the TPU, a split and a combine of every complex64 array)
per call. These tests pin the fused branch of every entry point in
``repro.kernels.ops`` to one top-level jit equation with a stable name that
holds the kernel, check that it still matches the float64 numpy reference
called eagerly and inside an outer ``jax.jit``, and that a frame over the
VMEM census still takes the unfused failover.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.kernels import ops

# entry -> (entry point, input shape, input dtype, program, kernel, numpy reference)
ENTRIES = {
    "fft": (ops.fft_kernel, (2, 8, 16), jnp.complex64, "repro_fft_kernel",
            "repro_fft_fused", np.fft.fft),
    "rfft": (ops.rfft_kernel, (2, 8, 16), jnp.float32, "repro_rfft_kernel",
             "repro_rfft_fused", np.fft.rfft),
    "irfft": (ops.irfft_kernel, (2, 8, 9), jnp.complex64, "repro_irfft_kernel",
              "repro_irfft_fused", np.fft.irfft),
    "fft2": (ops.fft2_kernel, (2, 8, 16), jnp.complex64, "repro_fft2_kernel",
             "repro_fft2_fused", np.fft.fft2),
    "rfft2": (ops.rfft2_kernel, (2, 8, 16), jnp.float32, "repro_rfft2_kernel",
              "repro_rfft2_fused", np.fft.rfft2),
    "irfft2": (ops.irfft2_kernel, (2, 8, 9), jnp.complex64, "repro_irfft2_kernel",
               "repro_irfft2_fused", np.fft.irfft2),
}


def _input(shape, dtype, seed):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal(shape)
    if dtype == jnp.complex64:
        x = x + 1j * rng.standard_normal(shape)
    return x.astype(dtype)


def _pallas_names(jaxpr):
    """Names of every ``pallas_call`` in ``jaxpr`` and the programs it calls."""
    names = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            names.append(eqn.params["name"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names += _pallas_names(sub)
    return names


@pytest.mark.parametrize("radix", [2, 4])
@pytest.mark.parametrize("entry", list(ENTRIES))
def test_fused_entry_is_one_program(entry, radix):
    fn, shape, dtype, program, kernel, _ = ENTRIES[entry]
    x = jnp.zeros(shape, dtype)
    jaxpr = jax.make_jaxpr(lambda a: fn(a, radix=radix))(x).jaxpr
    assert len(jaxpr.eqns) == 1, jaxpr
    (eqn,) = jaxpr.eqns
    assert eqn.primitive.name in ("pjit", "jit")
    assert eqn.params["name"] == program
    assert _pallas_names(jaxpr) == [kernel]


@pytest.mark.parametrize("outer_jit", [False, True], ids=["eager", "outer_jit"])
@pytest.mark.parametrize("entry", list(ENTRIES))
def test_fused_entry_matches_numpy(entry, outer_jit):
    fn, shape, dtype, _, _, ref = ENTRIES[entry]
    x = _input(shape, dtype, seed=sum(shape))
    if entry.startswith("irfft"):
        # a Hermitian half spectrum: the forward transform of real frames
        n = 2 * (shape[-1] - 1)
        frames = np.random.default_rng(n).standard_normal(shape[:-1] + (n,))
        x = (np.fft.rfft2 if entry == "irfft2" else np.fft.rfft)(frames).astype(np.complex64)
    call = (lambda a: fn(a, radix=4, interpret=True))
    if outer_jit:
        call = jax.jit(call)
    got = call(jnp.asarray(x))
    want = ref(x.astype(np.complex128 if dtype == jnp.complex64 else np.float64))
    expected = jnp.float32 if entry.startswith("irfft") else jnp.complex64
    assert got.dtype == expected
    assert got.shape == want.shape
    scale = max(1.0, float(np.max(np.abs(want))))
    np.testing.assert_allclose(np.asarray(got) / scale, want / scale, atol=1e-5)


# (entry, input shape, input dtype): one frame over the 2D VMEM census
# whose rows still fit the 1D kernel.
OVER_CENSUS = {
    "fft2": (ops.fft2_kernel, (1, 8, 4096), jnp.complex64, np.fft.fft2),
    "rfft2": (ops.rfft2_kernel, (1, 8, 4096), jnp.float32, np.fft.rfft2),
    "irfft2": (ops.irfft2_kernel, (1, 8, 2049), jnp.complex64, np.fft.irfft2),
}


@pytest.mark.parametrize("entry", list(OVER_CENSUS))
def test_frame_over_the_census_takes_the_failover(entry):
    fn, shape, dtype, ref = OVER_CENSUS[entry]
    h, w = shape[-2], 2 * (shape[-1] - 1) if entry == "irfft2" else shape[-1]
    assert not ops.fft2_fits_budget(h, w, real=entry != "fft2")
    x = _input(shape, dtype, seed=7)
    if entry == "irfft2":
        x = np.fft.rfft2(np.random.default_rng(7).standard_normal((1, h, w))).astype(
            np.complex64)
    jaxpr = jax.make_jaxpr(fn)(jnp.asarray(x)).jaxpr
    assert f"repro_{entry}_kernel" not in [e.params.get("name") for e in jaxpr.eqns]
    with obs.capture() as trace:
        got = fn(jnp.asarray(x))
    (failover,) = trace.select("kernel.failover")
    assert failover["kind"] == entry.replace("2", "2d")
    names = [e.name for e in trace]
    if entry != "irfft2":  # the inverse's failover dispatches without stage spans
        assert [n for n in names if n.startswith("fft.")] == ["fft.rows", "fft.columns"]
    assert "kernel.launch" not in names
    want = ref(x.astype(np.complex128 if dtype == jnp.complex64 else np.float64))
    scale = max(1.0, float(np.max(np.abs(want))))
    np.testing.assert_allclose(np.asarray(got) / scale, want / scale, atol=1e-5)
