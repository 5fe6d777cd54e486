"""Translation registration / motion correction via phase correlation.

The moco-workshop pipeline (PAPERS.md, ``/root/related``) corrects
inter-frame motion by estimating a rigid shift per frame and applying it
in k-space; the estimation workhorse is phase correlation — the
cross-power spectrum of two frames is a pure phase ramp whose inverse
transform is a delta at the displacement:

    R = F(ref) · conj(F(mov)) / |F(ref) · conj(F(mov))|
    corr = IFFT2(R)  →  peak at the shift

Whole-pixel estimation is one planned forward/inverse transform pair
(the two-for-one real path for camera/MRI magnitude frames). Subpixel
refinement is the Guizar-Sicairos upsampled-DFT trick: evaluate the
inverse transform on a tiny ``O(1.5·u)²`` grid around the coarse peak by
matrix-multiply DFT at ``u``× upsampling — no big zero-padded transform.

Conventions match ``skimage.registration.phase_cross_correlation``: the
returned ``(dy, dx)`` is the shift to APPLY to ``mov`` to register it
onto ``ref`` — ``apply_shift(mov, register_phase_correlation(ref, mov))
≈ ref``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

import repro.xfft as xfft
from repro.core.rfft import _is_real

__all__ = [
    "register_phase_correlation",
    "register_logpolar",
    "apply_shift",
    "hermitian_full",
]


def hermitian_full(rh: jax.Array, w: int) -> jax.Array:
    """Full-width spectrum from its Hermitian (..., H, W/2+1) half.

    A real frame's spectrum satisfies ``R[q, r] = conj(R[−q mod H, W−r])``,
    so the missing columns are a conjugated, double-flipped copy of
    columns ``1 .. W/2−1`` — no second (complex) transform needed. Used
    here to rebuild the full cross-power spectrum for subpixel
    refinement, and by :func:`repro.imaging.psd.fft2_psd` to return the
    full PSD off the two-for-one real path.
    """
    tail = jnp.conj(rh[..., :, 1:w - w // 2])        # cols 1 .. W/2-1
    tail = jnp.flip(tail, axis=-1)                   # -> cols W-1 .. W/2+1 order
    tail = jnp.roll(jnp.flip(tail, axis=-2), 1, axis=-2)  # row q -> (-q) mod H
    return jnp.concatenate([rh, tail], axis=-1)


def _upsampled_peak(r_full: jax.Array, coarse: jax.Array, upsample: int):
    """Refine per-item peaks by evaluating IFFT2(R) on a ±(region/2u)
    window around ``coarse`` at ``u``× upsampling (matrix-multiply DFT)."""
    h, w = r_full.shape[-2], r_full.shape[-1]
    region = int(math.ceil(1.5 * upsample))
    centre = region // 2
    grid = (jnp.arange(region, dtype=jnp.float32) - centre) / upsample
    fy = xfft.fftfreq(h, dtype=jnp.float32)                   # cycles/sample
    fx = xfft.fftfreq(w, dtype=jnp.float32)
    # Per-item sample positions around the coarse peak (broadcast batch).
    ys = coarse[..., 0:1] + grid                              # (..., region)
    xs = coarse[..., 1:2] + grid
    ey = jnp.exp(2j * math.pi * ys[..., :, None] * fy)        # (..., region, H)
    ex = jnp.exp(2j * math.pi * xs[..., :, None] * fx)        # (..., region, W)
    cc = jnp.einsum("...ah,...hw,...bw->...ab", ey, r_full, ex)
    flat = jnp.abs(cc).reshape(*cc.shape[:-2], region * region)
    idx = jnp.argmax(flat, axis=-1)
    dy = (idx // region).astype(jnp.float32)
    dx = (idx % region).astype(jnp.float32)
    return jnp.stack(
        [coarse[..., 0] + (dy - centre) / upsample,
         coarse[..., 1] + (dx - centre) / upsample],
        axis=-1,
    )


def register_phase_correlation(
    ref: jax.Array,
    mov: jax.Array,
    upsample_factor: int = 1,
    eps: float = 1e-12,
) -> jax.Array:
    """Estimate the (dy, dx) translation registering ``mov`` onto ``ref``.

    ``ref``/``mov``: (..., H, W), real or complex, leading axes batched —
    one planned transform pair serves the whole batch. Returns float32
    ``(..., 2)``. ``upsample_factor > 1`` adds subpixel refinement to
    within ``1/upsample_factor`` px (Guizar-Sicairos upsampled DFT).
    """
    ref = jnp.asarray(ref)
    mov = jnp.asarray(mov)
    if ref.shape != mov.shape:
        raise ValueError(
            f"ref and mov must share a shape, got {ref.shape} vs {mov.shape}"
        )
    if ref.ndim < 2:
        raise ValueError(f"need (..., H, W) frames, got shape {ref.shape}")
    h, w = ref.shape[-2], ref.shape[-1]
    real = _is_real(ref) and _is_real(mov)
    if real:
        fr_ = xfft.rfft2(ref)
        fm = xfft.rfft2(mov)
    else:
        fr_ = xfft.fft2(ref.astype(jnp.complex64))
        fm = xfft.fft2(mov.astype(jnp.complex64))
    r = fr_ * jnp.conj(fm)
    r = r / jnp.maximum(jnp.abs(r), eps)              # pure phase ramp
    corr = xfft.irfft2(r) if real else jnp.real(xfft.ifft2(r))
    idx = jnp.argmax(corr.reshape(*corr.shape[:-2], h * w), axis=-1)
    py = idx // w
    px = idx % w
    coarse = jnp.stack(
        [jnp.where(py > h // 2, py - h, py).astype(jnp.float32),
         jnp.where(px > w // 2, px - w, px).astype(jnp.float32)],
        axis=-1,
    )
    if upsample_factor <= 1:
        return coarse
    r_full = hermitian_full(r, w) if real else r
    return _upsampled_peak(r_full, coarse, int(upsample_factor))


def _logpolar_resample(mag: jax.Array) -> jax.Array:
    """Resample a centred (H, W) magnitude spectrum onto a log-polar grid.

    Rows sweep θ over [0, π) (a real frame's magnitude spectrum is
    point-symmetric, so the half-turn carries all the information and
    the axis stays circular for phase correlation); columns sweep radius
    log-uniformly from 1 to ``min(H, W)/2 − 1``. The output keeps the
    (H, W) shape, so both axes stay pow2 for the planned transforms that
    phase correlation runs next.
    """
    from jax.scipy.ndimage import map_coordinates

    h, w = mag.shape[-2], mag.shape[-1]
    n_theta, n_r = h, w
    rmax = min(h, w) / 2.0 - 1.0
    theta = jnp.arange(n_theta, dtype=jnp.float32) * (math.pi / n_theta)
    logr = jnp.exp(
        jnp.arange(n_r, dtype=jnp.float32) * (math.log(rmax) / (n_r - 1))
    )
    rows = h / 2.0 + logr[None, :] * jnp.sin(theta)[:, None]
    cols = w / 2.0 + logr[None, :] * jnp.cos(theta)[:, None]
    return map_coordinates(mag, [rows, cols], order=1, mode="constant")


def register_logpolar(
    ref: jax.Array, mov: jax.Array, upsample_factor: int = 10
):
    """Estimate the rotation + scale of ``mov`` relative to ``ref``.

    The Fourier-Mellin trick on the existing machinery: a rotation of
    the frame rotates its spectrum magnitude, an isotropic scale by
    ``s`` scales it by ``1/s`` — and on a log-polar resampling of the
    magnitude both become pure *translations* (rotation along θ, log-
    scale along log-r), which :func:`register_phase_correlation`
    already recovers to subpixel precision. The magnitude comes from
    :func:`repro.imaging.psd.fft2_psd` so the border cross artifact
    (which would anchor a spurious zero-motion peak) never enters.

    Returns ``(angle, scale)`` floats: ``mov`` looks like ``ref``
    rotated by ``angle`` radians (counter-clockwise, y-up convention)
    and magnified by ``scale`` about the centre; apply the inverse warp
    ``(-angle, 1/scale)`` to register ``mov`` onto ``ref``. Translation
    does not bias the estimate (magnitude spectra are shift-invariant)
    — recover it afterwards with :func:`register_phase_correlation` on
    the de-rotated frame. 2D frames only; the angle is recovered modulo
    π (magnitude spectra cannot tell a half-turn apart).
    """
    # lazy import: psd imports hermitian_full from this module
    from repro.imaging.psd import fft2_psd

    ref = jnp.asarray(ref)
    mov = jnp.asarray(mov)
    if ref.ndim != 2 or mov.ndim != 2:
        raise ValueError(
            f"register_logpolar takes single (H, W) frames, got "
            f"{ref.shape} and {mov.shape}"
        )
    if ref.shape != mov.shape:
        raise ValueError(
            f"ref and mov must share a shape, got {ref.shape} vs {mov.shape}"
        )
    h, w = ref.shape
    lp_ref = _logpolar_resample(jnp.log1p(jnp.abs(xfft.fftshift2(fft2_psd(ref)))))
    lp_mov = _logpolar_resample(jnp.log1p(jnp.abs(xfft.fftshift2(fft2_psd(mov)))))
    d_theta, d_logr = register_phase_correlation(
        lp_ref, lp_mov, upsample_factor=upsample_factor
    )
    rmax = min(h, w) / 2.0 - 1.0
    angle = float(d_theta) * (math.pi / h)
    scale = math.exp(float(d_logr) * (math.log(rmax) / (w - 1)))
    return angle, scale


def apply_shift(x: jax.Array, shift) -> jax.Array:
    """Translate ``x`` by ``shift = (dy, dx)`` (fractional ok) via the
    Fourier shift theorem: ``y[i, j] = x[i − dy, j − dx]`` with circular
    boundary. ``shift`` broadcasts over leading axes (``(..., 2)``); real
    frames stay on the two-for-one half-spectrum path end to end."""
    x = jnp.asarray(x)
    if x.ndim < 2:
        raise ValueError(f"need (..., H, W) frames, got shape {x.shape}")
    shift = jnp.asarray(shift, dtype=jnp.float32)
    if shift.shape[-1] != 2:
        raise ValueError(f"shift must end in (dy, dx), got shape {shift.shape}")
    h, w = x.shape[-2], x.shape[-1]
    dy = shift[..., 0][..., None, None]
    dx = shift[..., 1][..., None, None]
    fy = xfft.fftfreq(h, dtype=jnp.float32)[:, None]
    if _is_real(x):
        fx = xfft.rfftfreq(w, dtype=jnp.float32)[None, :]
        ramp = jnp.exp(-2j * math.pi * (fy * dy + fx * dx))
        return xfft.irfft2(xfft.rfft2(x) * ramp).astype(x.dtype)
    fx = xfft.fftfreq(w, dtype=jnp.float32)[None, :]
    ramp = jnp.exp(-2j * math.pi * (fy * dy + fx * dx))
    return xfft.ifft2(xfft.fft2(x) * ramp)
