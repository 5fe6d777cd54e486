"""Overlap-save tiled FFT convolution: frames bigger than any transform.

The paper's engine (and its software twin here) is happiest on frames
whose whole working set sits in VMEM; ``repro.kernels`` refuses to grow
past that and fails over to slower unfused passes. But imaging inputs —
stitched microscopy, holography holograms, wide-area correlation scenes
— are routinely far larger than any single power-of-two transform worth
running. Overlap-save is the classical answer: slide a VMEM-sized tile
with ``K − 1`` overlap across the frame, circularly convolve each tile
in the spectrum, keep each tile's valid interior, and the seams vanish
by construction.

The tile is a *planning* decision: small tiles waste work on overlap,
big tiles on padding — and past the fused kernels' working-set census
(``repro.kernels.ops.fft2_working_set``) they fall off the VMEM cliff.
``oaconvolve2`` therefore asks ``repro.plan`` (problem kind
``oaconv2d``) for the tile, and the answer is cached wisdom like any
other plan. Every transform in here goes through ``repro.xfft``: real
inputs ride the two-for-one half-spectrum path end to end.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

import repro.xfft as xfft
from repro.core.fft1d import _next_pow2
from repro.core.rfft import _is_real
from repro.plan.api import resolve_call

__all__ = ["oaconvolve2", "fftconv2", "matched_filter2"]


def _check_2d_pair(image: jax.Array, kernel: jax.Array, name: str):
    image = jnp.asarray(image)
    kernel = jnp.asarray(kernel)
    if image.ndim < 2 or kernel.ndim < 2:
        raise ValueError(
            f"{name} needs (..., H, W) image and (..., KH, KW) kernel, got "
            f"{image.shape} and {kernel.shape}"
        )
    return image, kernel


def _crop_mode(
    full: jax.Array, h: int, w: int, kh: int, kw: int, mode: str
) -> jax.Array:
    """Crop a full (H+KH−1, W+KW−1) convolution to ``mode`` (scipy names)."""
    if mode == "full":
        return full
    if mode == "same":
        top, left = (kh - 1) // 2, (kw - 1) // 2
        return full[..., top:top + h, left:left + w]
    if mode == "valid":
        if kh > h or kw > w:
            raise ValueError(
                f"valid-mode convolution needs kernel <= image, got "
                f"({kh}, {kw}) vs ({h}, {w})"
            )
        return full[..., kh - 1:h, kw - 1:w]
    raise ValueError(f'mode must be "full", "same" or "valid", got {mode!r}')


def _pad_tail(x: jax.Array, h: int, w: int) -> jax.Array:
    pad = [(0, 0)] * (x.ndim - 2)
    pad += [(0, h - x.shape[-2]), (0, w - x.shape[-1])]
    return jnp.pad(x, pad)


def _spectral_multiply(a: jax.Array, b: jax.Array, real: bool) -> jax.Array:
    """Circular convolution of equal-size frames through planned FFTs."""
    if real:
        return xfft.irfft2(xfft.rfft2(a) * xfft.rfft2(b))
    return xfft.ifft2(xfft.fft2(a) * xfft.fft2(b))


def fftconv2(
    image: jax.Array, kernel: jax.Array, mode: str = "full"
) -> jax.Array:
    """Linear 2D convolution via ONE padded transform pair (plan-backed).

    The reference and small-input path: both operands zero-pad to the
    power-of-two cover of (H+KH−1, W+KW−1) and multiply in the spectrum.
    Use :func:`oaconvolve2` when the padded frame outgrows a sensible
    single transform. Kernel leading axes broadcast against the image's.
    """
    image, kernel = _check_2d_pair(image, kernel, "fftconv2")
    h, w = image.shape[-2], image.shape[-1]
    kh, kw = kernel.shape[-2], kernel.shape[-1]
    fh, fw = h + kh - 1, w + kw - 1
    ph, pw = _next_pow2(fh), _next_pow2(fw)
    real = _is_real(image) and _is_real(kernel)
    if not real:
        image = image.astype(jnp.complex64)
        kernel = kernel.astype(jnp.complex64)
    full = _spectral_multiply(
        _pad_tail(image, ph, pw), _pad_tail(kernel, ph, pw), real
    )[..., :fh, :fw]
    return _crop_mode(full, h, w, kh, kw, mode)


def _gather_tiles(
    xp: jax.Array, th: int, tw: int, sh: int, sw: int, nbh: int, nbw: int
) -> jax.Array:
    """(..., PH, PW) -> (..., nbh, nbw, th, tw) overlapping tile stack."""
    hidx = jnp.arange(nbh)[:, None] * sh + jnp.arange(th)[None, :]
    widx = jnp.arange(nbw)[:, None] * sw + jnp.arange(tw)[None, :]
    tiles = xp[..., hidx, :]                 # (..., nbh, th, PW)
    tiles = tiles[..., widx]                 # (..., nbh, th, nbw, tw)
    return jnp.moveaxis(tiles, -2, -3)       # (..., nbh, nbw, th, tw)


def oaconvolve2(
    image: jax.Array,
    kernel: jax.Array,
    mode: str = "same",
    tile: Optional[Tuple[int, int]] = None,
) -> jax.Array:
    """Overlap-save tiled FFT convolution of (..., H, W) with (..., KH, KW).

    Handles images far larger than any single power-of-two transform:
    the frame streams through (TH, TW) tiles with (KH−1, KW−1) overlap,
    each tile one planned ``rfft2``/``irfft2`` (or complex) round trip,
    seams exact by construction. ``tile=None`` asks the planner (problem
    kind ``oaconv2d``) — the tile that best trades overlap waste against
    padding waste while the fused kernels' working set stays in VMEM.
    Kernel leading axes broadcast against the image's (one kernel, or
    one per batched frame). Matches :func:`fftconv2` to fp32 tolerance.
    """
    image, kernel = _check_2d_pair(image, kernel, "oaconvolve2")
    h, w = image.shape[-2], image.shape[-1]
    kh, kw = kernel.shape[-2], kernel.shape[-1]
    real = _is_real(image) and _is_real(kernel)
    if tile is None:
        plan = resolve_call(
            "oaconv2d",
            (h, w, kh, kw),
            dtype="float32" if real else "complex64",
        )
        tile = plan.tile
    th, tw = int(tile[0]), int(tile[1])
    if th < kh or tw < kw:
        raise ValueError(
            f"tile {(th, tw)} smaller than kernel {(kh, kw)}: the "
            "overlap-save step T-K+1 would be empty"
        )
    fh, fw = h + kh - 1, w + kw - 1
    sh, sw = th - kh + 1, tw - kw + 1
    nbh, nbw = math.ceil(fh / sh), math.ceil(fw / sw)
    if nbh * nbw == 1:
        # One tile covers the whole output: the single-transform path is
        # the same arithmetic without the gather.
        return fftconv2(image, kernel, mode=mode)
    if not real:
        image = image.astype(jnp.complex64)
        kernel = kernel.astype(jnp.complex64)
    ph = (kh - 1) + (nbh - 1) * sh + th - (kh - 1)   # = (nbh-1)*sh + th
    pw = (nbw - 1) * sw + tw
    pad = [(0, 0)] * (image.ndim - 2)
    pad += [(kh - 1, ph - (kh - 1) - h), (kw - 1, pw - (kw - 1) - w)]
    xp = jnp.pad(image, pad)
    tiles = _gather_tiles(xp, th, tw, sh, sw, nbh, nbw)
    kf = _pad_tail(kernel, th, tw)[..., None, None, :, :]  # broadcast tiles
    out = _spectral_multiply(tiles, kf, real)
    valid = out[..., kh - 1:, kw - 1:]                # (..., nbh, nbw, sh, sw)
    joined = jnp.moveaxis(valid, -3, -2)              # (..., nbh, sh, nbw, sw)
    full = joined.reshape(*joined.shape[:-4], nbh * sh, nbw * sw)
    return _crop_mode(full[..., :fh, :fw], h, w, kh, kw, mode)


def matched_filter2(
    scene: jax.Array,
    template: jax.Array,
    mode: str = "same",
    tile: Optional[Tuple[int, int]] = None,
) -> jax.Array:
    """Cross-correlate ``scene`` with ``template`` at any scene size —
    the paper's correlation-pattern-recognition workload, tiled.

    ``corr[i, j] = Σ scene[i+u, j+v]·conj(template[u, v])``, computed as
    an overlap-save convolution with the conjugate-flipped template, so
    scenes far beyond :func:`repro.spectral.correlate2`'s equal-size,
    single-transform contract still stream through VMEM-sized tiles.
    The peak of the result locates the template.
    """
    template = jnp.asarray(template)
    flipped = jnp.conj(jnp.flip(template, axis=(-2, -1)))
    return oaconvolve2(scene, flipped, mode=mode, tile=tile)
