"""Unit tests for the paper's 1D engine (all variants).

Hypothesis property tests live in test_fft1d_properties.py so this module
collects even when hypothesis is not installed."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.xfft as xfft
from repro.core.fft1d import (
    bit_reversal_permutation,
    butterfly_counts,
    fft_routing_tables,
)

VARIANTS = ("looped", "unrolled", "stockham")


def fft(x, axis=-1, variant=None):
    """The front door's fft, on engine ``variant`` (None: the planner's)."""
    with xfft.config(variant=variant):
        return xfft.fft(x, axis=axis)


def _crand(rng, shape):
    return (rng.standard_normal(shape) + 1j * rng.standard_normal(shape)).astype(
        np.complex64
    )


@pytest.mark.parametrize("n", [2, 4, 8, 32, 128, 512, 2048])
@pytest.mark.parametrize("variant", VARIANTS)
def test_matches_numpy(rng, n, variant):
    x = _crand(rng, (3, n))
    ref = np.fft.fft(x.astype(np.complex128))
    got = np.asarray(fft(jnp.asarray(x), variant=variant))
    scale = max(1.0, np.max(np.abs(ref)))
    np.testing.assert_allclose(got / scale, ref / scale, atol=5e-6)


@pytest.mark.parametrize("variant", VARIANTS)
def test_variants_agree(rng, variant):
    x = _crand(rng, (2, 256))
    base = np.asarray(fft(jnp.asarray(x), variant="looped"))
    got = np.asarray(fft(jnp.asarray(x), variant=variant))
    np.testing.assert_allclose(got, base, atol=1e-3)


@pytest.mark.parametrize("axis", [0, 1, 2, -1, -2])
def test_axis_argument(rng, axis):
    x = _crand(rng, (8, 4, 16))
    got = np.asarray(fft(jnp.asarray(x), axis=axis))
    ref = np.fft.fft(x, axis=axis)
    np.testing.assert_allclose(got, ref, atol=1e-3)


def test_real_input_promoted(rng):
    x = rng.standard_normal((5, 64)).astype(np.float32)
    got = np.asarray(fft(jnp.asarray(x)))
    np.testing.assert_allclose(got, np.fft.fft(x), atol=1e-3)


def test_non_pow2_rejected():
    with pytest.raises(ValueError):
        fft(jnp.zeros((2, 12)))


def test_jit_and_grad():
    x = jnp.ones((2, 16), jnp.float32)

    @jax.jit
    def f(v):
        return jnp.sum(jnp.abs(fft(v)) ** 2)

    g = jax.grad(f)(x)
    assert g.shape == x.shape and bool(jnp.isfinite(g).all())


def test_bit_reversal_is_involution():
    for n in (2, 8, 64, 1024):
        p = bit_reversal_permutation(n)
        assert (p[p] == np.arange(n)).all()


def test_routing_tables_cover_all_positions():
    for n in (8, 64):
        idx_a, idx_b, tw, unperm = fft_routing_tables(n)
        for s in range(idx_a.shape[0]):
            union = np.sort(np.concatenate([idx_a[s], idx_b[s]]))
            assert (union == np.arange(n)).all()
            assert (idx_b[s] - idx_a[s] == (1 << s)).all()
            assert (np.sort(unperm[s]) == np.arange(n)).all()


def test_butterfly_counts_match_paper_tables():
    # Paper Table 2: proposed N/2 BUs vs traditional (N/2)·log2N.
    c_prop = butterfly_counts(1024, proposed=True)
    c_trad = butterfly_counts(1024, proposed=False)
    assert c_prop["butterfly_units"] == 512
    assert c_trad["butterfly_units"] == 512 * 10
    assert c_prop["adders_subtractors"] == 1024
    assert c_trad["adders_subtractors"] == 1024 * 10
    # eq. 5: area ratio = 1/log2 N
    assert c_prop["butterfly_units"] / c_trad["butterfly_units"] == 1 / 10
