"""Real-input FFTs (two-for-one Hermitian packing) vs the numpy oracles."""

import jax.numpy as jnp
import numpy as np
import pytest

import repro.xfft as xfft

VARIANTS = ["looped", "unrolled", "stockham", "radix4"]


def _on(variant, transform, x, **kw):
    """``transform`` through the front door on engine ``variant`` (None or
    "auto": the planner's)."""
    with xfft.config(variant=None if variant == "auto" else variant):
        return transform(x, **kw)


def rfft(x, axis=-1, variant=None):
    return _on(variant, xfft.rfft, x, axis=axis)


def irfft(y, axis=-1, variant=None):
    return _on(variant, xfft.irfft, y, axis=axis)


def rfft2(x, variant=None):
    return _on(variant, xfft.rfft2, x)


def irfft2(y, variant=None):
    return _on(variant, xfft.irfft2, y)


@pytest.mark.parametrize("shape", [(1, 2), (3, 8), (2, 64), (4, 128), (1, 1024)])
@pytest.mark.parametrize("variant", VARIANTS)
def test_rfft_matches_numpy(rng, shape, variant):
    x = rng.standard_normal(shape).astype(np.float32)
    got = np.asarray(rfft(jnp.asarray(x), variant=variant))
    ref = np.fft.rfft(x)
    assert got.shape == ref.shape
    scale = max(1.0, np.max(np.abs(ref)))
    np.testing.assert_allclose(got / scale, ref / scale, atol=1e-5)


@pytest.mark.parametrize("shape", [(3, 8), (2, 64), (1, 256)])
@pytest.mark.parametrize("variant", VARIANTS)
def test_irfft_roundtrip(rng, shape, variant):
    x = rng.standard_normal(shape).astype(np.float32)
    rt = np.asarray(irfft(rfft(jnp.asarray(x), variant=variant), variant=variant))
    np.testing.assert_allclose(rt, x, atol=1e-4)


def test_rfft_axis_argument(rng):
    x = rng.standard_normal((16, 5)).astype(np.float32)
    got = np.asarray(rfft(jnp.asarray(x), axis=0))
    np.testing.assert_allclose(got, np.fft.rfft(x, axis=0), atol=1e-4)
    rt = np.asarray(irfft(jnp.asarray(got), axis=0))
    np.testing.assert_allclose(rt, x, atol=1e-4)


@pytest.mark.parametrize("hw", [(8, 8), (16, 64), (64, 16), (32, 32)])
@pytest.mark.parametrize("variant", ["stockham", "radix4"])
def test_rfft2_matches_numpy(rng, hw, variant):
    x = rng.standard_normal((2, *hw)).astype(np.float32)
    got = np.asarray(rfft2(jnp.asarray(x), variant=variant))
    ref = np.fft.rfft2(x)
    assert got.shape == ref.shape
    scale = max(1.0, np.max(np.abs(ref)))
    np.testing.assert_allclose(got / scale, ref / scale, atol=1e-5)


@pytest.mark.parametrize("hw", [(8, 8), (16, 32)])
@pytest.mark.parametrize("variant", ["stockham", "radix4", "auto"])
def test_irfft2_roundtrip(rng, hw, variant):
    x = rng.standard_normal((2, *hw)).astype(np.float32)
    rt = np.asarray(irfft2(rfft2(jnp.asarray(x), variant=variant), variant=variant))
    np.testing.assert_allclose(rt, x, atol=1e-4)


def test_rfft_auto_plans_under_real_kind(rng):
    """An unpinned rfft resolves through the rfft1d problem kind."""
    from repro.plan import default_cache, problem_key

    x = rng.standard_normal((4, 32)).astype(np.float32)
    got = np.asarray(rfft(jnp.asarray(x), variant="auto"))
    np.testing.assert_allclose(got, np.fft.rfft(x), atol=1e-4)
    key = problem_key("rfft1d", (4, 32), dtype="float32")
    assert default_cache().get(key) is not None


def test_rfft_rejects_complex_and_bad_lengths(rng):
    with pytest.raises(TypeError):
        rfft(jnp.ones((4, 8), jnp.complex64))
    with pytest.raises(ValueError):
        rfft(jnp.ones((4, 12), jnp.float32))  # not a power of two
    with pytest.raises(ValueError):
        irfft(jnp.ones((4, 8), jnp.complex64))  # width 8 is not N/2+1


def test_hermitian_half_spectrum_is_complete(rng):
    """The half spectrum reconstructs the full one by conjugate symmetry."""
    x = rng.standard_normal((2, 16)).astype(np.float32)
    half = np.asarray(rfft(jnp.asarray(x)))
    full = np.fft.fft(x)
    mirrored = np.conj(half[..., 1:-1][..., ::-1])
    np.testing.assert_allclose(
        np.concatenate([half, mirrored], axis=-1), full, atol=1e-4
    )
