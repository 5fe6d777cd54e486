"""SENSE operator contracts: shapes, batching, and — the property every
iterative reconstruction leans on — exact adjointness of the
forward/adjoint pair under the ortho centered transform, in single AND
double precision."""

import numpy as np
import pytest
from repro._x64 import enable_x64

import repro.xfft as xfft
from repro import mri


def _complex_rand(rng, shape, dtype=np.complex64):
    return (rng.standard_normal(shape) + 1j * rng.standard_normal(shape)).astype(
        dtype
    )


def test_forward_adjoint_shapes(phantom, smaps):
    k = mri.sense_forward(phantom, smaps)
    assert k.shape == smaps.shape                      # (C, H, W)
    img = mri.sense_adjoint(k, smaps)
    assert img.shape == phantom.shape                  # (H, W)


def test_leading_axes_batch(phantom, smaps):
    batch = np.stack([phantom, phantom[::-1].copy()])
    k = np.asarray(mri.sense_forward(batch, smaps))
    assert k.shape == (2, *smaps.shape)
    single = np.asarray(mri.sense_forward(batch[1], smaps))
    np.testing.assert_allclose(k[1], single, atol=1e-5)
    img = np.asarray(mri.sense_adjoint(k, smaps))
    assert img.shape == batch.shape


def test_unitarity_with_normalised_maps(phantom, smaps):
    """Birdcage maps are RSS-normalised, so AᴴA = Σ_c |S_c|² = I when
    fully sampled — the adjoint inverts the forward exactly."""
    x = phantom.astype(np.complex64)
    back = np.asarray(mri.sense_adjoint(mri.sense_forward(x, smaps), smaps))
    np.testing.assert_allclose(back, x, atol=1e-5)


def test_adjointness_single(rng, smaps):
    """<A u, v> == <u, Aᴴ v> — the defining identity, at the masked
    operator (the one CG actually inverts)."""
    h, w = smaps.shape[-2:]
    mask = np.asarray(mri.uniform_mask((h, w), 2))
    u = _complex_rand(rng, (h, w))
    v = _complex_rand(rng, smaps.shape)
    au = np.asarray(mri.sense_forward(u, smaps, mask))
    ahv = np.asarray(mri.sense_adjoint(v, smaps, mask))
    lhs = np.vdot(au, v)
    rhs = np.vdot(u, ahv)
    assert abs(lhs - rhs) <= 1e-4 * abs(lhs)


def test_adjointness_double(rng):
    """The same identity at double precision: the centered transforms
    must not silently downcast complex128 inside an x64 scope."""
    with enable_x64():
        with xfft.config(precision="double"):
            smaps = np.asarray(mri.birdcage_maps(4, 32)).astype(np.complex128)
            mask = np.asarray(mri.uniform_mask((32, 32), 2))
            u = _complex_rand(rng, (32, 32), np.complex128)
            v = _complex_rand(rng, smaps.shape, np.complex128)
            au = np.asarray(mri.sense_forward(u, smaps, mask))
            ahv = np.asarray(mri.sense_adjoint(v, smaps, mask))
    assert au.dtype == np.complex128 and ahv.dtype == np.complex128
    lhs = np.vdot(au, v)
    rhs = np.vdot(u, ahv)
    assert abs(lhs - rhs) <= 1e-12 * abs(lhs)


def test_apply_mask_bool_and_float(rng, smaps):
    k = _complex_rand(rng, smaps.shape)
    m = np.asarray(mri.uniform_mask(smaps.shape[-2:], 2))
    np.testing.assert_array_equal(
        np.asarray(mri.apply_mask(k, m.astype(bool))),
        np.asarray(mri.apply_mask(k, m)),
    )
    masked = np.asarray(mri.apply_mask(k, m))
    assert masked.dtype == k.dtype
    assert np.all(masked[:, m == 0] == 0)


def test_rss_of_normalised_maps_is_one(smaps):
    np.testing.assert_allclose(
        np.asarray(mri.rss_combine(smaps)), 1.0, atol=1e-5
    )


def test_shape_validation():
    with pytest.raises(ValueError, match="image"):
        mri.sense_forward(np.zeros(8), np.zeros((4, 8, 8)))
    with pytest.raises(ValueError, match="smaps"):
        mri.sense_forward(np.zeros((8, 8)), np.zeros((8, 8)))
    with pytest.raises(ValueError, match="does not match"):
        mri.sense_forward(np.zeros((8, 8)), np.zeros((4, 8, 16)))
    with pytest.raises(ValueError, match="kspace"):
        mri.sense_adjoint(np.zeros((8, 8)), np.zeros((4, 8, 8)))
    with pytest.raises(ValueError, match="does not match"):
        mri.sense_adjoint(np.zeros((4, 8, 8)), np.zeros((2, 8, 8)))
