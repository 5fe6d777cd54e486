"""Real-input FFTs via two-for-one Hermitian packing — half the work.

Every workload the paper targets (medical imaging, holography, correlation
recognition) feeds the transform *real* data, whose spectrum is conjugate
symmetric: Y[k] = conj(Y[N-k]). Computing the full complex FFT therefore
does 2× the arithmetic and moves 2× the bytes actually required. The classic
remedy — pack the N real samples as N/2 complex numbers z[j] = x[2j] +
i·x[2j+1], run ONE half-size complex FFT, and untangle the two interleaved
spectra with the symmetry recombination

    Y[k] = Xe[k] + W_N^k · Xo[k],   k = 0..N/2

— is the software twin of the paper's area reuse: the same butterfly engine,
half the stages' worth of data.

The ``*_impl`` functions are the jnp engines' real-input bodies under a
concrete variant (the fused Pallas kernels run the pack + half-size panel
+ recombination in one VMEM residency in ``repro.kernels``). Each 1D pass
(the row pass and the column pass of the 2D paths) is one compiled
program, dispatched under its ``repro.obs`` span (``fft.rows``,
``fft.columns``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import obs
from repro.core.fft1d import Variant, _fft_jnp, _ifft_jnp, fft_impl, ifft_impl

__all__ = ["rfft_impl", "irfft_impl", "rfft2_impl", "irfft2_impl"]


def _is_real(x) -> bool:
    return not jnp.issubdtype(jnp.asarray(x).dtype, jnp.complexfloating)


def _ensure_real(x: jax.Array, name: str) -> jax.Array:
    """Validate real input WITHOUT touching its dtype (the engine — or the
    precision-aware front door — owns the float width)."""
    x = jnp.asarray(x)
    if not _is_real(x):
        raise TypeError(f"{name} expects real input; use fft/fft2 for complex")
    return x


def _rfft_jnp(x: jax.Array, n: int, variant: str) -> jax.Array:
    """Pack N reals as N/2 complex, half-size FFT, symmetry recombination."""
    m = n // 2
    z = (x[..., 0::2] + 1j * x[..., 1::2]).astype(jnp.complex64)
    zf = _fft_jnp(z, variant) if m > 1 else z
    k = jnp.arange(m + 1)
    zk = jnp.take(zf, k % m, axis=-1)               # Z[k], with Z[M] = Z[0]
    zmk = jnp.conj(jnp.take(zf, (-k) % m, axis=-1))  # conj(Z[(M-k) mod M])
    xe = 0.5 * (zk + zmk)                           # spectrum of even samples
    xo = -0.5j * (zk - zmk)                         # spectrum of odd samples
    w = jnp.exp(-2j * jnp.pi * k / n).astype(jnp.complex64)
    return xe + w * xo


def _irfft_jnp(y: jax.Array, n: int, variant: str) -> jax.Array:
    """Invert the recombination, one half-size IFFT, de-interleave."""
    m = n // 2
    # np.fft.irfft semantics: DC and Nyquist bins of a Hermitian spectrum
    # are real — discard any imaginary part there.
    edge = jnp.arange(m + 1)
    y = jnp.where((edge == 0) | (edge == m), jnp.real(y).astype(jnp.complex64), y)
    k = jnp.arange(m)
    yk = y[..., :m]
    ymk = jnp.conj(jnp.flip(y[..., 1:], axis=-1))   # conj(Y[M-k]), k = 0..M-1
    xe = 0.5 * (yk + ymk)
    xo = 0.5 * (yk - ymk) * jnp.exp(2j * jnp.pi * k / n).astype(jnp.complex64)
    z = xe + 1j * xo
    zi = _ifft_jnp(z, variant) if m > 1 else z
    out = jnp.stack([jnp.real(zi), jnp.imag(zi)], axis=-1)
    return out.reshape(*zi.shape[:-1], n).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("axis", "variant"))
def repro_jnp_rfft_pass(x: jax.Array, axis: int, variant: str) -> jax.Array:
    """The real forward pass of a jnp engine along ``axis`` as one program:
    the float32 cast, the moves each way, the pack, the half-size FFT and
    the recombination."""
    x = jnp.moveaxis(x.astype(jnp.float32), axis, -1)
    return jnp.moveaxis(_rfft_jnp(x, x.shape[-1], variant), -1, axis)


@functools.partial(jax.jit, static_argnames=("axis", "variant"))
def repro_jnp_irfft_pass(y: jax.Array, axis: int, variant: str) -> jax.Array:
    """The real inverse pass of a jnp engine along ``axis`` as one program."""
    y = jnp.moveaxis(y.astype(jnp.complex64), axis, -1)
    out = _irfft_jnp(y, 2 * (y.shape[-1] - 1), variant)
    return jnp.moveaxis(out, -1, axis)


def rfft_impl(x: jax.Array, axis: int = -1, *, variant: Variant) -> jax.Array:
    """Real-input FFT along ``axis`` -> non-redundant half spectrum
    (..., N/2+1) complex, one pass program. N must be a power of two >= 2."""
    x = _ensure_real(x, "rfft")
    return repro_jnp_rfft_pass(x, axis=axis % x.ndim, variant=variant)


def irfft_impl(y: jax.Array, axis: int = -1, *, variant: Variant) -> jax.Array:
    """Inverse of :func:`rfft_impl`: (..., N/2+1) half spectrum -> real
    (..., N), one pass program."""
    y = jnp.asarray(y)
    return repro_jnp_irfft_pass(y, axis=axis % y.ndim, variant=variant)


def rfft2_impl(x: jax.Array, *, variant: Variant) -> jax.Array:
    """2D real-input FFT over the last two axes: row rfft then full column
    FFT -> (..., H, W/2+1) complex."""
    x = _ensure_real(x, "rfft2")
    with obs.span("fft.rows"):
        y = rfft_impl(x, axis=-1, variant=variant)
    with obs.span("fft.columns"):
        return fft_impl(y, axis=-2, variant=variant)


def irfft2_impl(y: jax.Array, *, variant: Variant) -> jax.Array:
    """Inverse of :func:`rfft2_impl`: (..., H, W/2+1) -> real (..., H, W)."""
    with obs.span("fft.columns"):
        z = ifft_impl(y, axis=-2, variant=variant)
    with obs.span("fft.rows"):
        return irfft_impl(z, axis=-1, variant=variant)
