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

The ``*_impl`` functions are the engine entries (any variant, including
``"fused"``/``"fused_r4"`` — the Pallas kernels that run the pack +
half-size panel + recombination in one VMEM residency — and ``"auto"``,
planned through ``repro.plan`` under the ``rfft1d``/``rfft2d`` problem
kinds). The public names are deprecated aliases of the ``repro.xfft``
front door, which adds ``norm=`` conventions and plan-backed dispatch.

On the jnp engines each 1D pass (the row pass and the column pass of the
2D paths) is one compiled program, dispatched under its ``repro.obs``
span (``fft.rows``, ``fft.columns``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro import obs
from repro.core._deprecation import warn_deprecated
from repro.core.fft1d import (
    BUILTIN_VARIANTS,
    Variant,
    _check_pow2,
    _fft_jnp,
    _ifft_jnp,
    fft_impl,
    ifft_impl,
)

__all__ = ["rfft", "irfft", "rfft2", "irfft2"]

_FUSED = ("fused", "fused_r4")


def _ensure_real(x: jax.Array, name: str) -> jax.Array:
    """Validate real input WITHOUT touching its dtype (the engine — or the
    precision-aware xfft front door — owns the float width)."""
    x = jnp.asarray(x)
    if jnp.issubdtype(x.dtype, jnp.complexfloating):
        raise TypeError(f"{name} expects real input; use fft/fft2 for complex")
    return x


def _resolve(kind: str, shape, variant: Variant, direction: str = "fwd") -> Variant:
    if variant != "auto":
        return variant
    from repro.plan.api import resolve  # lazy: plan imports core

    return resolve(kind, tuple(shape), dtype="float32", direction=direction).variant


def _radix(variant: Variant) -> int:
    return 4 if variant == "fused_r4" else 2


def _rfft_jnp(x: jax.Array, n: int, variant: Variant) -> jax.Array:
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


def _irfft_jnp(y: jax.Array, n: int, variant: Variant) -> jax.Array:
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


def rfft_impl(x: jax.Array, axis: int = -1, variant: Variant = "auto") -> jax.Array:
    """Real-input FFT along ``axis`` -> non-redundant half spectrum
    (..., N/2+1) complex. N must be a power of two >= 2."""
    orig = x
    x = _ensure_real(x, "rfft")
    user_axis = axis
    axis = axis % x.ndim
    n = x.shape[axis]
    _check_pow2(n, axis=user_axis)
    key_shape = x.shape[:axis] + x.shape[axis + 1:] + (n,)
    variant = _resolve("rfft1d", key_shape, variant)
    if variant not in BUILTIN_VARIANTS:
        # Registry fallback gets the caller's ORIGINAL array (an x64
        # engine must do its own asarray/moveaxis inside enable_x64).
        from repro.engines import apply_engine

        return apply_engine(variant, "rfft1d", orig, axis=axis)
    if variant not in _FUSED:
        return repro_jnp_rfft_pass(x, axis=axis, variant=variant)
    from repro.kernels.ops import rfft_kernel  # lazy: kernels import core

    x = jnp.moveaxis(x.astype(jnp.float32), axis, -1)
    return jnp.moveaxis(rfft_kernel(x, radix=_radix(variant)), -1, axis)


def irfft_impl(y: jax.Array, axis: int = -1, variant: Variant = "auto") -> jax.Array:
    """Inverse of :func:`rfft_impl`: (..., N/2+1) half spectrum -> real (..., N)."""
    orig = y
    y = jnp.asarray(y)
    user_axis = axis
    axis = axis % y.ndim
    n = 2 * (y.shape[axis] - 1)
    if n < 2 or n & (n - 1):
        raise ValueError(
            f"axis {user_axis} has a half spectrum of width {y.shape[axis]}; "
            "irfft requires width N/2+1 with N a power of two"
        )
    key_shape = y.shape[:axis] + y.shape[axis + 1:] + (n,)
    variant = _resolve("rfft1d", key_shape, variant, direction="inv")
    if variant not in BUILTIN_VARIANTS:
        from repro.engines import apply_engine  # lazy: registry fallback

        return apply_engine(variant, "rfft1d", orig, direction="inv", axis=axis)
    if variant not in _FUSED:
        return repro_jnp_irfft_pass(y, axis=axis, variant=variant)
    from repro.kernels.ops import irfft_kernel  # lazy: kernels import core

    y = jnp.moveaxis(y.astype(jnp.complex64), axis, -1)
    return jnp.moveaxis(irfft_kernel(y, radix=_radix(variant)), -1, axis)


def rfft2_impl(x: jax.Array, variant: Variant = "auto") -> jax.Array:
    """2D real-input FFT over the last two axes: row rfft then full column
    FFT -> (..., H, W/2+1) complex."""
    orig = x
    x = _ensure_real(x, "rfft2")
    variant = _resolve("rfft2d", x.shape, variant)
    if variant not in BUILTIN_VARIANTS:
        from repro.engines import apply_engine  # lazy: registry fallback

        return apply_engine(variant, "rfft2d", orig)
    x = x.astype(jnp.float32)
    if variant in _FUSED:
        from repro.kernels.ops import rfft2_kernel  # lazy: kernels import core

        return rfft2_kernel(x, radix=_radix(variant))
    with obs.span("fft.rows"):
        y = rfft_impl(x, axis=-1, variant=variant)
    with obs.span("fft.columns"):
        return fft_impl(y, axis=-2, variant=variant)


def irfft2_impl(y: jax.Array, variant: Variant = "auto") -> jax.Array:
    """Inverse of :func:`rfft2_impl`: (..., H, W/2+1) -> real (..., H, W)."""
    orig = y
    y = jnp.asarray(y)
    half = y.shape[-1]
    w = 2 * (half - 1)
    variant = _resolve("rfft2d", y.shape[:-1] + (w,), variant, direction="inv")
    if variant not in BUILTIN_VARIANTS:
        from repro.engines import apply_engine  # lazy: registry fallback

        return apply_engine(variant, "rfft2d", orig, direction="inv")
    y = y.astype(jnp.complex64)
    if variant in _FUSED:
        from repro.kernels.ops import irfft2_kernel  # lazy: kernels import core

        return irfft2_kernel(y, radix=_radix(variant))
    with obs.span("fft.columns"):
        z = ifft_impl(y, axis=-2, variant=variant)
    with obs.span("fft.rows"):
        return irfft_impl(z, axis=-1, variant=variant)


# --------------------- deprecated public entry points ---------------------


def rfft(
    x: jax.Array, axis: int = -1, variant: Optional[Variant] = None
) -> jax.Array:
    """Deprecated alias of :func:`repro.xfft.rfft` (kept for old call sites)."""
    warn_deprecated("repro.core.rfft.rfft", "repro.xfft.rfft")
    from repro import xfft  # lazy: xfft builds on this module

    if variant is None or variant == "auto":
        return xfft.rfft(x, axis=axis)
    with xfft.config(variant=variant):
        return xfft.rfft(x, axis=axis)


def irfft(
    y: jax.Array, axis: int = -1, variant: Optional[Variant] = None
) -> jax.Array:
    """Deprecated alias of :func:`repro.xfft.irfft` (kept for old call sites)."""
    warn_deprecated("repro.core.rfft.irfft", "repro.xfft.irfft")
    from repro import xfft  # lazy: xfft builds on this module

    if variant is None or variant == "auto":
        return xfft.irfft(y, axis=axis)
    with xfft.config(variant=variant):
        return xfft.irfft(y, axis=axis)


def rfft2(x: jax.Array, variant: Optional[Variant] = None) -> jax.Array:
    """Deprecated alias of :func:`repro.xfft.rfft2` (kept for old call sites)."""
    warn_deprecated("repro.core.rfft.rfft2", "repro.xfft.rfft2")
    from repro import xfft  # lazy: xfft builds on this module

    if variant is None or variant == "auto":
        return xfft.rfft2(x)
    with xfft.config(variant=variant):
        return xfft.rfft2(x)


def irfft2(y: jax.Array, variant: Optional[Variant] = None) -> jax.Array:
    """Deprecated alias of :func:`repro.xfft.irfft2` (kept for old call sites)."""
    warn_deprecated("repro.core.rfft.irfft2", "repro.xfft.irfft2")
    from repro import xfft  # lazy: xfft builds on this module

    if variant is None or variant == "auto":
        return xfft.irfft2(y)
    with xfft.config(variant=variant):
        return xfft.irfft2(y)
