"""Periodic-plus-smooth decomposition: edge-artifact-free spectra.

The DFT treats every frame as one period of a torus. A natural image's
opposite borders do not match, so the implicit wrap is a step edge, and
that step stamps a bright cross (energy smeared along both frequency
axes) over the whole spectrum — fatal for correlation recognition and
k-space analysis, the paper's own motivating workloads. Moisan's
periodic-plus-smooth decomposition splits the frame ``x = p + s`` where
``s`` (the *smooth* component) is the harmonic image carrying all the
border mismatch and ``p`` (the *periodic* component) tiles seamlessly.

Mahmood et al. ("2D DFT with Simultaneous Edge Artifact Removal",
PAPERS.md) make this real-time on tiled FFT hardware by solving the
smooth component *in the spectrum*: ``s`` solves a discrete Poisson
equation whose right-hand side is nonzero only on the frame border, so
its spectrum is a closed form over TWO 1D FFTs of the border-difference
vectors — no second 2D transform:

    v̂[q, r] = B̂1[r]·(1 − e^{2πiq/H}) + B̂2[q]·(1 − e^{2πir/W})
    ŝ[q, r] = v̂[q, r] / (2cos(2πq/H) + 2cos(2πr/W) − 4),   ŝ[0,0] = 0

where ``b1 = x[H−1,:] − x[0,:]`` and ``b2 = x[:,W−1] − x[:,0]``. That is
what :func:`fft2_psd` computes: one planned ``fft2`` plus two planned 1D
``fft`` calls, every transform resolved through ``repro.plan``.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

import repro.xfft as xfft
from repro.core.rfft import _is_real
from repro.imaging.registration import hermitian_full

# The ONE argument contract: axis canonicalization (bounds-checked, named
# errors), norm validation and post-engine scaling all come from the xfft
# front door, so the smooth term below can never drift out of sync with
# the fft2 term it is subtracted from.
from repro.xfft._transforms import _canon_axes, _check_norm, _scale

__all__ = ["psd_decompose", "fft2_psd", "smooth_spectrum"]


def _to_last_two(x: jax.Array, axes: Tuple[int, int], name: str):
    x = jnp.asarray(x)
    if x.ndim < 2:
        raise ValueError(f"{name} needs at least a 2D image, got shape {x.shape}")
    if len(axes) != 2:
        raise ValueError(f"{name} decomposes exactly 2 axes, got {tuple(axes)}")
    canon = _canon_axes(axes, x.ndim, name)
    moved = canon != (x.ndim - 2, x.ndim - 1)
    if moved:
        x = jnp.moveaxis(x, canon, (-2, -1))
    return x, canon, moved


def smooth_spectrum(x: jax.Array) -> jax.Array:
    """Spectrum (backward norm) of the smooth component of ``(..., H, W)``.

    The in-spectrum solve above: two planned 1D FFTs of the border
    differences, a closed-form Poisson division, no 2D transform.
    """
    x = jnp.asarray(x)
    h, w = x.shape[-2], x.shape[-1]
    cdt = x.dtype if jnp.issubdtype(x.dtype, jnp.complexfloating) else jnp.complex64
    b1 = (x[..., -1, :] - x[..., 0, :]).astype(cdt)   # (..., W)
    b2 = (x[..., :, -1] - x[..., :, 0]).astype(cdt)   # (..., H)
    bhat1 = xfft.fft(b1)                              # planned length-W pass
    bhat2 = xfft.fft(b2)                              # planned length-H pass
    q = jnp.arange(h, dtype=jnp.float32)
    r = jnp.arange(w, dtype=jnp.float32)
    fq = 1.0 - jnp.exp(2j * math.pi * q / h).astype(cdt)   # (H,)
    fr = 1.0 - jnp.exp(2j * math.pi * r / w).astype(cdt)   # (W,)
    vhat = bhat1[..., None, :] * fq[:, None] + bhat2[..., :, None] * fr[None, :]
    denom = (
        2.0 * jnp.cos(2.0 * math.pi * q / h)[:, None]
        + 2.0 * jnp.cos(2.0 * math.pi * r / w)[None, :]
        - 4.0
    )
    denom = denom.at[0, 0].set(1.0)                   # avoid 0/0 at DC
    shat = vhat / denom.astype(cdt)
    return shat.at[..., 0, 0].set(0.0)                # smooth has zero mean


def _smooth_spectrum_half(x: jax.Array) -> jax.Array:
    """Half-width smooth spectrum ``shat[..., :, :W/2+1]`` of a REAL frame.

    The two-for-one route of :func:`smooth_spectrum`: real border
    differences take ``rfft`` (half the border-pass arithmetic), the
    row-axis half is Hermitian-extended in-place (1D flip+conj, no
    transform), and the Poisson division only ever runs on the half the
    real 2D path actually consumes.
    """
    x = jnp.asarray(x)
    h, w = x.shape[-2], x.shape[-1]
    wh = w // 2 + 1
    b1 = x[..., -1, :] - x[..., 0, :]                 # (..., W) real
    b2 = x[..., :, -1] - x[..., :, 0]                 # (..., H) real
    bhat1 = xfft.rfft(b1)                             # (..., W/2+1)
    bhat2h = xfft.rfft(b2)                            # (..., H/2+1)
    # full-length row spectrum by Hermitian symmetry: B2[q] = conj(B2[H-q])
    tail = jnp.conj(jnp.flip(bhat2h[..., 1:h - h // 2], axis=-1))
    bhat2 = jnp.concatenate([bhat2h, tail], axis=-1)  # (..., H)
    cdt = bhat1.dtype
    q = jnp.arange(h, dtype=jnp.float32)
    r = jnp.arange(wh, dtype=jnp.float32)
    fq = 1.0 - jnp.exp(2j * math.pi * q / h).astype(cdt)
    fr = 1.0 - jnp.exp(2j * math.pi * r / w).astype(cdt)
    vhat = bhat1[..., None, :wh] * fq[:, None] + bhat2[..., :, None] * fr[None, :]
    denom = (
        2.0 * jnp.cos(2.0 * math.pi * q / h)[:, None]
        + 2.0 * jnp.cos(2.0 * math.pi * r / w)[None, :]
        - 4.0
    )
    denom = denom.at[0, 0].set(1.0)
    shat = vhat / denom.astype(cdt)
    return shat.at[..., 0, 0].set(0.0)


def psd_decompose(
    x: jax.Array, axes: Tuple[int, int] = (-2, -1)
) -> Tuple[jax.Array, jax.Array]:
    """Split ``x`` into ``(periodic, smooth)`` with ``periodic + smooth == x``.

    The periodic component tiles seamlessly (opposite borders match), so
    its spectrum carries no cross artifact; the smooth component is the
    harmonic border-mismatch image. Leading axes are batched.
    """
    x, canon, moved = _to_last_two(x, axes, "psd_decompose")
    if _is_real(x):
        # two-for-one: the smooth component of a real frame is real, so
        # its spectrum is Hermitian — one irfft2 of the half-spectrum
        # replaces the complex ifft2 + real projection
        smooth = xfft.irfft2(_smooth_spectrum_half(x)).astype(x.dtype)
    else:
        smooth = xfft.ifft2(smooth_spectrum(x))
    periodic = x - smooth
    if moved:
        periodic = jnp.moveaxis(periodic, (-2, -1), canon)
        smooth = jnp.moveaxis(smooth, (-2, -1), canon)
    return periodic, smooth


def fft2_psd(
    x: jax.Array,
    axes: Tuple[int, int] = (-2, -1),
    norm: Optional[str] = None,
) -> jax.Array:
    """2D spectrum of the *periodic* component of ``x`` — ``fft2`` minus
    the in-spectrum smooth solve, i.e. Mahmood et al.'s simultaneous
    edge-artifact removal. Same shape, layout and ``norm`` conventions as
    :func:`repro.xfft.fft2`; one extra pair of 1D border FFTs is the whole
    overhead. Real frames take the two-for-one route throughout —
    ``rfft2`` plus the half-width smooth solve — and the Hermitian
    half-spectrum is expanded to full width only here, where the full
    PSD is the return contract."""
    norm = _check_norm(norm)
    x, canon, moved = _to_last_two(x, axes, "fft2_psd")
    h, w = x.shape[-2], x.shape[-1]
    if _is_real(x):
        shat_h = _scale(_smooth_spectrum_half(x), norm, h * w, forward=True)
        phat = hermitian_full(xfft.rfft2(x, norm=norm) - shat_h, w)
    else:
        shat = _scale(smooth_spectrum(x), norm, h * w, forward=True)
        phat = xfft.fft2(x, norm=norm) - shat
    return jnp.moveaxis(phat, (-2, -1), canon) if moved else phat
