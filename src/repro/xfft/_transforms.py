"""The eight xfft transforms + N-D helpers, all plan-backed.

Every function here follows the same dispatch pipeline:

1. validate axes/norm and (scipy-style) resize to ``n``/``s`` if given —
   errors name the offending axis and size;
2. move the transform axes last (the engines' canonical layout);
3. resolve the whole call through :func:`repro.plan.api.resolve_call`
   (plan cache -> scoped config overrides -> concrete engine from the
   ``repro.engines`` registry, capability-filtered by the scope's
   precision and backend restriction);
4. run the ``repro.core`` engine implementation under that variant,
   through the resilience degradation ladder
   (:func:`repro.resilience.run_plan`): an engine failure quarantines
   the engine for this problem key and retries the next-best rung;
5. apply the ``norm`` scaling on top of the engines' native convention
   (forward unscaled, inverse 1/N — i.e. ``"backward"``).

Each transform call runs inside an ``xfft.call`` span (``repro.obs``)
carrying the transform's name (``kind``), the input's shape and dtype:
the root of the call's span records when profiling is on, whose children
are ``plan.resolve``, ``engine.apply`` and the engine's stage spans.

Precision handling: under ``xfft.config(precision="double")`` every
public entry point runs its whole body inside ``jax.enable_x64`` — that
is the only way jax lets 64-bit dtypes survive the plumbing (moveaxis,
pad, roll and friends re-canonicalize dtypes when x64 is off), and it
makes the double path work whether or not ``JAX_ENABLE_X64`` is set
process-wide. The planner then resolves to an engine registered with the
``"double"`` capability (``reference_x64``) and the call is complex128
end to end.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from repro import obs
from repro._x64 import enable_x64 as _enable_x64

from repro.core.fft1d import _check_pow2 as _core_check_pow2
from repro.core.fft1d import canonical_axis
from repro.core.fft1d import fft_impl as _fft_impl
from repro.core.fft1d import ifft_impl as _ifft_impl
from repro.core.fft2d import fft2_impl as _fft2_impl
from repro.core.fft2d import fftshift2 as _core_fftshift2
from repro.core.fft2d import ifft2_impl as _ifft2_impl
from repro.core.fft2d import ifftshift2 as _core_ifftshift2
from repro.core.rfft import _ensure_real  # one real-input contract
from repro.core.rfft import irfft2_impl as _irfft2_impl
from repro.core.rfft import irfft_impl as _irfft_impl
from repro.core.rfft import rfft2_impl as _rfft2_impl
from repro.core.rfft import rfft_impl as _rfft_impl
from repro.plan.api import resolve_call
from repro.plan.plan import NORMS
from repro.resilience.ladder import run_plan as _run_plan
from repro.xfft._config import get_config

__all__ = [
    "fft", "ifft", "fft2", "ifft2", "fftn", "ifftn",
    "rfft", "irfft", "rfft2", "irfft2", "rfftn", "irfftn",
    "fftshift", "ifftshift", "fftshift2", "ifftshift2",
    "fftfreq", "rfftfreq",
]


def _precision_scope(fn):
    """Run the wrapped entry point under ``jax.enable_x64`` when the scoped
    precision is double, so 64-bit dtypes survive every jnp op inside."""

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        if get_config().precision == "double":
            with _enable_x64():
                return fn(*args, **kwargs)
        return fn(*args, **kwargs)

    return wrapper


def _transform(fn):
    """A front-door transform: an ``xfft.call`` span around the call, which
    runs under :func:`_precision_scope`."""
    scoped = _precision_scope(fn)
    kind = fn.__name__

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        x = args[0] if args else kwargs.get("x")
        dtype = getattr(x, "dtype", None)
        with obs.span("xfft.call", kind=kind, shape=tuple(getattr(x, "shape", ())),
                      dtype=None if dtype is None else str(dtype)):
            return scoped(*args, **kwargs)

    return wrapper


def _cdtype():
    """The scope's complex dtype (what inverse entry points cast input to)."""
    return jnp.complex128 if get_config().precision == "double" else jnp.complex64


def _rdtype():
    """The scope's real dtype (what real-input entry points cast to)."""
    return jnp.float64 if get_config().precision == "double" else jnp.float32


def _real_input(x, name: str):
    """Validate real input and cast it to the scope's float width."""
    return _ensure_real(x, name).astype(_rdtype())


def _check_norm(norm: Optional[str]) -> str:
    if norm is None:
        return "backward"
    if norm not in NORMS:
        raise ValueError(
            f'norm must be one of {NORMS} (or None for "backward"), got {norm!r}'
        )
    return norm


# one bounds check for the whole stack (same helper the engines use)
_canon_axis = canonical_axis


def _canon_axes(
    axes: Sequence[int], ndim: int, name: str
) -> Tuple[int, ...]:
    canon = tuple(_canon_axis(a, ndim, name) for a in axes)
    if len(set(canon)) != len(canon):
        raise ValueError(f"{name}: axes {tuple(axes)} name an axis twice")
    return canon


def _check_pow2(n: int, axis: int, name: str) -> None:
    """The satellite error contract: name the offending axis AND size
    (one shared message — ``repro.core.fft1d._check_pow2`` — so the
    wording can't drift between the front door and the engines)."""
    del name  # entry point named by the traceback; the contract names axis+size
    _core_check_pow2(n, axis=axis)


def _resize_axis(x: jax.Array, n: int, axis: int) -> jax.Array:
    """scipy-style ``n``/``s`` handling: crop or zero-pad along ``axis``."""
    cur = x.shape[axis]
    if n == cur:
        return x
    if n < cur:
        idx = [slice(None)] * x.ndim
        idx[axis] = slice(0, n)
        return x[tuple(idx)]
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, n - cur)
    return jnp.pad(x, pad)


def _scale(y: jax.Array, norm: str, n: int, forward: bool) -> jax.Array:
    """Norm correction on top of the engines' backward convention."""
    if norm == "backward":
        return y
    if norm == "ortho":
        factor = 1.0 / math.sqrt(n) if forward else math.sqrt(n)
    else:  # "forward"
        factor = 1.0 / n if forward else float(n)
    # Match the factor's width to the data so a complex128 result is not
    # dragged down by f32 rounding of the scale (and a single-precision
    # result never pays an f64 promotion).
    wide = y.dtype in (jnp.complex128, jnp.float64)
    return y * jnp.asarray(factor, dtype=jnp.float64 if wide else jnp.float32)


def _moved_shape(shape: Tuple[int, ...], axis: int) -> Tuple[int, ...]:
    """The plan-key shape: ``axis`` moved last (the engines' layout)."""
    return shape[:axis] + shape[axis + 1:] + (shape[axis],)


# ------------------------------ 1D complex ------------------------------


@_transform
def fft(x, n: Optional[int] = None, axis: int = -1, norm: Optional[str] = None):
    """1D FFT along ``axis``; scipy.fft-compatible, plan-backed dispatch."""
    norm = _check_norm(norm)
    x = jnp.asarray(x)
    ax = _canon_axis(axis, x.ndim, "fft")
    if n is not None:
        x = _resize_axis(x, int(n), ax)
    length = x.shape[ax]
    _check_pow2(length, ax, "fft")
    plan = resolve_call("fft1d", _moved_shape(x.shape, ax))
    y = _run_plan(plan, lambda v: _fft_impl(x, axis=ax, variant=v))
    return _scale(y, norm, length, forward=True)


@_transform
def ifft(x, n: Optional[int] = None, axis: int = -1, norm: Optional[str] = None):
    """Inverse 1D FFT along ``axis`` (norm-aware, plan-backed)."""
    norm = _check_norm(norm)
    x = jnp.asarray(x)
    ax = _canon_axis(axis, x.ndim, "ifft")
    if n is not None:
        x = _resize_axis(x, int(n), ax)
    length = x.shape[ax]
    _check_pow2(length, ax, "ifft")
    plan = resolve_call("fft1d", _moved_shape(x.shape, ax), direction="inv")
    y = _run_plan(plan, lambda v: _ifft_impl(x, axis=ax, variant=v))
    return _scale(y, norm, length, forward=False)


# ------------------------------ 2D complex ------------------------------


def _prep_2d(x, s, axes, norm, name):
    """Shared 2D plumbing: validate, resize, move axes to (-2, -1)."""
    norm = _check_norm(norm)
    x = jnp.asarray(x)
    if x.ndim < 2:
        raise ValueError(f"{name} needs at least a 2D array, got shape {x.shape}")
    if len(axes) != 2:
        raise ValueError(f"{name} transforms exactly 2 axes, got {tuple(axes)}")
    canon = _canon_axes(axes, x.ndim, name)
    if s is not None:
        if len(s) != 2:
            raise ValueError(f"{name}: s must have 2 entries, got {tuple(s)}")
        for target, ax in zip(s, canon):
            x = _resize_axis(x, int(target), ax)
    for ax in canon:
        _check_pow2(x.shape[ax], ax, name)
    moved = canon != (x.ndim - 2, x.ndim - 1)
    if moved:
        x = jnp.moveaxis(x, canon, (-2, -1))
    return x, norm, canon, moved


def _unmove_2d(y, canon, moved):
    return jnp.moveaxis(y, (-2, -1), canon) if moved else y


@_transform
def fft2(x, s=None, axes=(-2, -1), norm: Optional[str] = None):
    """2D FFT over ``axes``; scipy.fft-compatible, plan-backed dispatch."""
    x, norm, canon, moved = _prep_2d(x, s, axes, norm, "fft2")
    h, w = x.shape[-2], x.shape[-1]
    plan = resolve_call("fft2d", x.shape)
    y = _run_plan(plan, lambda v: _fft2_impl(x, variant=v))
    return _unmove_2d(_scale(y, norm, h * w, forward=True), canon, moved)


@_transform
def ifft2(x, s=None, axes=(-2, -1), norm: Optional[str] = None):
    """Inverse 2D FFT over ``axes`` (norm-aware, plan-backed)."""
    x, norm, canon, moved = _prep_2d(x, s, axes, norm, "ifft2")
    h, w = x.shape[-2], x.shape[-1]
    plan = resolve_call("fft2d", x.shape, direction="inv")
    y = _run_plan(plan, lambda v: _ifft2_impl(x, variant=v))
    return _unmove_2d(_scale(y, norm, h * w, forward=False), canon, moved)


# ------------------------------ N-D complex ------------------------------


def _fftn_axes(x, s, axes, name):
    if axes is None:
        axes = tuple(range(x.ndim)) if s is None else \
            tuple(range(x.ndim - len(s), x.ndim))
    axes = tuple(int(a) for a in axes)
    if s is not None and len(s) != len(axes):
        raise ValueError(
            f"{name}: s has {len(s)} entries for {len(axes)} axes"
        )
    return axes


@_transform
def fftn(x, s=None, axes=None, norm: Optional[str] = None):
    """N-D FFT: separable 1D passes (a plan per axis); 2-axis calls take
    the dedicated ``fft2d`` planning kind via :func:`fft2`."""
    x = jnp.asarray(x)
    axes = _fftn_axes(x, s, axes, "fftn")
    if len(axes) == 2:
        return fft2(x, s=s, axes=axes, norm=norm)
    norm = _check_norm(norm)
    _canon_axes(axes, x.ndim, "fftn")  # distinctness + bounds up front
    total = 1
    for i, ax in enumerate(axes):
        if s is not None:
            x = _resize_axis(x, int(s[i]), _canon_axis(ax, x.ndim, "fftn"))
        total *= x.shape[_canon_axis(ax, x.ndim, "fftn")]
        x = fft(x, axis=ax)
    return _scale(x, norm, total, forward=True)


@_transform
def ifftn(x, s=None, axes=None, norm: Optional[str] = None):
    """Inverse N-D FFT (see :func:`fftn`)."""
    x = jnp.asarray(x)
    axes = _fftn_axes(x, s, axes, "ifftn")
    if len(axes) == 2:
        return ifft2(x, s=s, axes=axes, norm=norm)
    norm = _check_norm(norm)
    _canon_axes(axes, x.ndim, "ifftn")
    total = 1
    for i, ax in enumerate(axes):
        if s is not None:
            x = _resize_axis(x, int(s[i]), _canon_axis(ax, x.ndim, "ifftn"))
        total *= x.shape[_canon_axis(ax, x.ndim, "ifftn")]
        x = ifft(x, axis=ax)
    return _scale(x, norm, total, forward=False)


# ------------------------------- real input -------------------------------




@_transform
def rfft(x, n: Optional[int] = None, axis: int = -1, norm: Optional[str] = None):
    """Real-input FFT -> non-redundant half spectrum (..., N/2+1)."""
    norm = _check_norm(norm)
    x = _real_input(x, "rfft")
    ax = _canon_axis(axis, x.ndim, "rfft")
    if n is not None:
        x = _resize_axis(x, int(n), ax)
    length = x.shape[ax]
    _check_pow2(length, ax, "rfft")
    plan = resolve_call("rfft1d", _moved_shape(x.shape, ax), dtype="float32")
    y = _run_plan(plan, lambda v: _rfft_impl(x, axis=ax, variant=v))
    return _scale(y, norm, length, forward=True)


@_transform
def irfft(x, n: Optional[int] = None, axis: int = -1, norm: Optional[str] = None):
    """Inverse of :func:`rfft`: half spectrum -> real signal of length ``n``
    (default ``2*(width-1)``)."""
    norm = _check_norm(norm)
    x = jnp.asarray(x).astype(_cdtype())
    ax = _canon_axis(axis, x.ndim, "irfft")
    length = int(n) if n is not None else 2 * (x.shape[ax] - 1)
    _check_pow2(length, ax, "irfft")
    # numpy semantics: the spectrum is cropped/zero-padded to n//2+1 bins.
    x = _resize_axis(x, length // 2 + 1, ax)
    key_shape = _moved_shape(x.shape, ax)[:-1] + (length,)
    plan = resolve_call("rfft1d", key_shape, dtype="float32", direction="inv")
    y = _run_plan(plan, lambda v: _irfft_impl(x, axis=ax, variant=v))
    return _scale(y, norm, length, forward=False)


@_transform
def rfft2(x, s=None, axes=(-2, -1), norm: Optional[str] = None):
    """2D real-input FFT -> (..., H, W/2+1) half spectrum, plan-backed."""
    x = _real_input(x, "rfft2")
    x, norm, canon, moved = _prep_2d(x, s, axes, norm, "rfft2")
    h, w = x.shape[-2], x.shape[-1]
    plan = resolve_call("rfft2d", x.shape, dtype="float32")
    y = _run_plan(plan, lambda v: _rfft2_impl(x, variant=v))
    return _unmove_2d(_scale(y, norm, h * w, forward=True), canon, moved)


@_transform
def irfft2(x, s=None, axes=(-2, -1), norm: Optional[str] = None):
    """Inverse of :func:`rfft2`: (..., H, W/2+1) -> real (..., H, W)."""
    norm = _check_norm(norm)
    x = jnp.asarray(x).astype(_cdtype())
    if x.ndim < 2:
        raise ValueError(f"irfft2 needs at least a 2D array, got shape {x.shape}")
    if len(axes) != 2:
        raise ValueError(f"irfft2 transforms exactly 2 axes, got {tuple(axes)}")
    if s is not None and len(s) != 2:
        raise ValueError(f"irfft2: s must have 2 entries, got {tuple(s)}")
    canon = _canon_axes(axes, x.ndim, "irfft2")
    moved = canon != (x.ndim - 2, x.ndim - 1)
    if moved:
        x = jnp.moveaxis(x, canon, (-2, -1))
    h = int(s[0]) if s is not None else x.shape[-2]
    w = int(s[1]) if s is not None else 2 * (x.shape[-1] - 1)
    _check_pow2(h, canon[0], "irfft2")
    _check_pow2(w, canon[1], "irfft2")
    x = _resize_axis(_resize_axis(x, h, -2), w // 2 + 1, -1)
    plan = resolve_call(
        "rfft2d", x.shape[:-1] + (w,), dtype="float32", direction="inv"
    )
    y = _run_plan(plan, lambda v: _irfft2_impl(x, variant=v))
    return _unmove_2d(_scale(y, norm, h * w, forward=False), canon, moved)


# ------------------------------ N-D real ------------------------------


@_transform
def rfftn(x, s=None, axes=None, norm: Optional[str] = None):
    """N-D real-input FFT: the two-for-one ``rfft`` along the LAST of
    ``axes``, complex passes over the rest — a real array never round-trips
    through a full complex ``fftn`` (half the arithmetic and traffic on the
    innermost, largest pass). 1- and 2-axis calls take the dedicated
    ``rfft1d``/``rfft2d`` planning kinds."""
    x = _real_input(x, "rfftn")
    axes = _fftn_axes(x, s, axes, "rfftn")
    if len(axes) == 1:
        return rfft(x, n=None if s is None else int(s[0]), axis=axes[0], norm=norm)
    if len(axes) == 2:
        return rfft2(x, s=s, axes=axes, norm=norm)
    norm = _check_norm(norm)
    canon = _canon_axes(axes, x.ndim, "rfftn")
    if s is not None:
        for target, ax in zip(s, canon):
            x = _resize_axis(x, int(target), ax)
    total = 1
    for ax in canon:
        total *= x.shape[ax]
    y = rfft(x, axis=canon[-1])
    for ax in canon[:-1]:
        y = fft(y, axis=ax)
    return _scale(y, norm, total, forward=True)


@_transform
def irfftn(x, s=None, axes=None, norm: Optional[str] = None):
    """Inverse of :func:`rfftn`: complex inverse passes over the leading
    axes, then the half-spectrum ``irfft`` along the last -> real output."""
    axes_in = axes
    x = jnp.asarray(x).astype(_cdtype())
    axes = _fftn_axes(x, s, axes_in, "irfftn")
    if len(axes) == 1:
        return irfft(x, n=None if s is None else int(s[0]), axis=axes[0], norm=norm)
    if len(axes) == 2:
        return irfft2(x, s=s, axes=axes, norm=norm)
    norm = _check_norm(norm)
    canon = _canon_axes(axes, x.ndim, "irfftn")
    total = 1
    for i, ax in enumerate(canon[:-1]):
        if s is not None:
            x = _resize_axis(x, int(s[i]), ax)
        total *= x.shape[ax]
        x = ifft(x, axis=ax)
    last = canon[-1]
    n_last = int(s[-1]) if s is not None else 2 * (x.shape[last] - 1)
    total *= n_last
    y = irfft(x, n=n_last, axis=last)
    return _scale(y, norm, total, forward=False)


# ------------------------------- shifts -------------------------------


@_precision_scope
def fftshift(x, axes=None):
    """Move the zero-frequency bin to the centre (numpy-compatible)."""
    x = jnp.asarray(x)
    if axes is None:
        axes = tuple(range(x.ndim))
    elif isinstance(axes, int):
        axes = (axes,)
    axes = _canon_axes(axes, x.ndim, "fftshift")
    return jnp.roll(x, [x.shape[a] // 2 for a in axes], axes)


@_precision_scope
def ifftshift(x, axes=None):
    """Exact inverse of :func:`fftshift` (correct for odd lengths too)."""
    x = jnp.asarray(x)
    if axes is None:
        axes = tuple(range(x.ndim))
    elif isinstance(axes, int):
        axes = (axes,)
    axes = _canon_axes(axes, x.ndim, "ifftshift")
    return jnp.roll(x, [-(x.shape[a] // 2) for a in axes], axes)


@_precision_scope
def fftshift2(x):
    """Centre the zero-frequency bin of the trailing two axes."""
    return _core_fftshift2(jnp.asarray(x))


@_precision_scope
def ifftshift2(x):
    """Exact inverse of :func:`fftshift2` (sign-correct for odd lengths)."""
    return _core_ifftshift2(jnp.asarray(x))


# ---------------------------- sample frequencies ----------------------------


def _freq_width_ctx(dtype):
    """Context that lets an EXPLICIT 64-bit dtype pin survive: outside a
    double scope jax would silently canonicalize a float64 request down to
    float32, which is the one thing a pinned width must never do."""
    import contextlib

    import numpy as np

    if dtype is not None and np.dtype(dtype).itemsize == 8:
        return _enable_x64()
    return contextlib.nullcontext()


@_precision_scope
def fftfreq(n, d: float = 1.0, *, dtype=None):
    """Sample frequencies of an ``n``-point FFT (scipy.fft parity).

    Bin ``k`` of :func:`fft` oscillates at ``fftfreq(n, d)[k]`` cycles per
    unit of the sample spacing ``d``. Pure index arithmetic — no engine —
    but it lives here so frequency grids follow the same precision scope
    as the transforms they index (``dtype=`` pins a width explicitly,
    honored whatever the ambient scope).
    """
    n = int(n)
    if n <= 0:
        raise ValueError(f"fftfreq needs a positive sample count, got {n}")
    with _freq_width_ctx(dtype):
        dt = dtype if dtype is not None else _rdtype()
        k = jnp.concatenate([
            jnp.arange(0, (n - 1) // 2 + 1, dtype=dt),
            jnp.arange(-(n // 2), 0, dtype=dt),
        ])
        return k / jnp.asarray(n * d, dtype=dt)


@_precision_scope
def rfftfreq(n, d: float = 1.0, *, dtype=None):
    """Sample frequencies of the :func:`rfft` half spectrum (scipy parity):
    the ``n // 2 + 1`` non-negative bins of :func:`fftfreq`."""
    n = int(n)
    if n <= 0:
        raise ValueError(f"rfftfreq needs a positive sample count, got {n}")
    with _freq_width_ctx(dtype):
        dt = dtype if dtype is not None else _rdtype()
        return jnp.arange(0, n // 2 + 1, dtype=dt) / jnp.asarray(n * d, dtype=dt)
