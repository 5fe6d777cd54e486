"""Hypothesis property tests for the 1D engine (Parseval, roundtrip,
linearity, shift theorem, conjugate symmetry).

Guarded with importorskip: the whole module skips when hypothesis is not
installed (it is a test extra, not a runtime dependency)."""

import numpy as np
import pytest

pytest.importorskip("hypothesis")

import jax.numpy as jnp  # noqa: E402
from hypothesis import given, settings  # noqa: E402
from hypothesis import strategies as st  # noqa: E402

from repro.xfft import fft, ifft  # noqa: E402


def _crand(rng, shape):
    return (rng.standard_normal(shape) + 1j * rng.standard_normal(shape)).astype(
        np.complex64
    )


array_strategy = st.tuples(
    st.integers(min_value=1, max_value=4),  # batch
    st.integers(min_value=1, max_value=7),  # log2 N
    st.integers(min_value=0, max_value=2**31 - 1),  # seed
)


@settings(max_examples=25, deadline=None)
@given(array_strategy)
def test_parseval(params):
    b, logn, seed = params
    n = 1 << logn
    rng = np.random.default_rng(seed)
    x = _crand(rng, (b, n))
    y = np.asarray(fft(jnp.asarray(x)))
    lhs = np.sum(np.abs(x) ** 2, axis=-1)
    rhs = np.sum(np.abs(y) ** 2, axis=-1) / n
    np.testing.assert_allclose(lhs, rhs, rtol=1e-3, atol=1e-3)


@settings(max_examples=25, deadline=None)
@given(array_strategy)
def test_roundtrip(params):
    b, logn, seed = params
    n = 1 << logn
    rng = np.random.default_rng(seed)
    x = _crand(rng, (b, n))
    rt = np.asarray(ifft(fft(jnp.asarray(x))))
    np.testing.assert_allclose(rt, x, atol=1e-3)


@settings(max_examples=25, deadline=None)
@given(array_strategy, st.integers(min_value=0, max_value=2**31 - 1))
def test_linearity(params, seed2):
    b, logn, seed = params
    n = 1 << logn
    r1, r2 = np.random.default_rng(seed), np.random.default_rng(seed2)
    x, y = _crand(r1, (b, n)), _crand(r2, (b, n))
    a = 0.7 - 0.3j
    lhs = np.asarray(fft(jnp.asarray(a * x + y)))
    rhs = a * np.asarray(fft(jnp.asarray(x))) + np.asarray(fft(jnp.asarray(y)))
    np.testing.assert_allclose(lhs, rhs, atol=2e-3)


@settings(max_examples=25, deadline=None)
@given(array_strategy)
def test_time_shift_theorem(params):
    b, logn, seed = params
    n = 1 << logn
    rng = np.random.default_rng(seed)
    x = _crand(rng, (b, n))
    shift = rng.integers(0, n)
    y_shifted = np.asarray(fft(jnp.asarray(np.roll(x, shift, axis=-1))))
    k = np.arange(n)
    phase = np.exp(-2j * np.pi * k * shift / n)
    y_expected = np.asarray(fft(jnp.asarray(x))) * phase
    np.testing.assert_allclose(y_shifted, y_expected, atol=5e-3)


@settings(max_examples=25, deadline=None)
@given(array_strategy)
def test_real_input_conjugate_symmetry(params):
    b, logn, seed = params
    n = 1 << logn
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((b, n)).astype(np.float32)
    y = np.asarray(fft(jnp.asarray(x)))
    # Y[k] == conj(Y[N-k])
    sym = np.conj(y[..., (-np.arange(n)) % n])
    np.testing.assert_allclose(y, sym, atol=2e-3)
    # DC bin is the plain sum.
    np.testing.assert_allclose(y[..., 0].real, x.sum(-1), rtol=1e-3, atol=1e-3)
