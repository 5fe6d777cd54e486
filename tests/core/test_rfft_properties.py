"""Hypothesis property tests for the real-input (two-for-one) path:
rfft/rfft2 against the jnp.fft oracles, Hermitian round-trips, and the
radix-4 engine's parity with radix-2 and jnp.fft.

Guarded with importorskip: the whole module skips when hypothesis is not
installed (it is a test extra, not a runtime dependency)."""

import numpy as np
import pytest

pytest.importorskip("hypothesis")

import jax.numpy as jnp  # noqa: E402
from hypothesis import given, settings  # noqa: E402
from hypothesis import strategies as st  # noqa: E402

import repro.xfft as xfft  # noqa: E402
from repro.xfft import irfft, irfft2, rfft, rfft2  # noqa: E402


def fft(x, variant):
    """The front door's fft on engine ``variant``."""
    with xfft.config(variant=variant):
        return xfft.fft(x)

array_strategy = st.tuples(
    st.integers(min_value=1, max_value=4),  # batch
    st.integers(min_value=1, max_value=7),  # log2 N
    st.integers(min_value=0, max_value=2**31 - 1),  # seed
)

frame_strategy = st.tuples(
    st.integers(min_value=2, max_value=5),  # log2 H
    st.integers(min_value=1, max_value=6),  # log2 W
    st.integers(min_value=0, max_value=2**31 - 1),  # seed
)


@settings(max_examples=25, deadline=None)
@given(array_strategy)
def test_rfft_matches_jnp(params):
    b, logn, seed = params
    n = 1 << logn
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((b, n)).astype(np.float32)
    got = np.asarray(rfft(jnp.asarray(x)))
    ref = np.asarray(jnp.fft.rfft(jnp.asarray(x)))
    scale = max(1.0, np.max(np.abs(ref)))
    np.testing.assert_allclose(got / scale, ref / scale, atol=1e-5)


@settings(max_examples=25, deadline=None)
@given(array_strategy)
def test_irfft_rfft_roundtrip(params):
    b, logn, seed = params
    n = 1 << logn
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((b, n)).astype(np.float32)
    rt = np.asarray(irfft(rfft(jnp.asarray(x))))
    np.testing.assert_allclose(rt, x, atol=1e-3)


@settings(max_examples=20, deadline=None)
@given(frame_strategy)
def test_rfft2_matches_jnp(params):
    logh, logw, seed = params
    h, w = 1 << logh, 1 << logw
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((h, w)).astype(np.float32)
    got = np.asarray(rfft2(jnp.asarray(x)))
    ref = np.asarray(jnp.fft.rfft2(jnp.asarray(x)))
    scale = max(1.0, np.max(np.abs(ref)))
    np.testing.assert_allclose(got / scale, ref / scale, atol=1e-5)


@settings(max_examples=20, deadline=None)
@given(frame_strategy)
def test_irfft2_rfft2_roundtrip(params):
    """Hermitian-symmetry round trip: irfft2(rfft2(x)) recovers x."""
    logh, logw, seed = params
    h, w = 1 << logh, 1 << logw
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((h, w)).astype(np.float32)
    rt = np.asarray(irfft2(rfft2(jnp.asarray(x))))
    np.testing.assert_allclose(rt, x, atol=1e-3)


@settings(max_examples=25, deadline=None)
@given(array_strategy)
def test_radix4_matches_radix2_and_jnp(params):
    b, logn, seed = params
    n = 1 << logn
    rng = np.random.default_rng(seed)
    x = (rng.standard_normal((b, n)) + 1j * rng.standard_normal((b, n))).astype(
        np.complex64
    )
    r4 = np.asarray(fft(jnp.asarray(x), variant="radix4"))
    r2 = np.asarray(fft(jnp.asarray(x), variant="stockham"))
    ref = np.asarray(jnp.fft.fft(jnp.asarray(x)))
    scale = max(1.0, np.max(np.abs(ref)))
    np.testing.assert_allclose(r4 / scale, r2 / scale, atol=1e-5)
    np.testing.assert_allclose(r4 / scale, ref / scale, atol=1e-5)
