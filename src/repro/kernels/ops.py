"""Public jit'd entry points for the FFT kernels.

Complex in/out convenience wrappers around the (re, im) kernel ABI, with
platform dispatch: real TPUs run the compiled kernels, CPU runs them in
interpret mode (the kernel body executes in Python — bit-identical logic),
and any other backend is refused.

  fft_kernel(x)    — fused 1D FFT (one HBM round trip)       [proposed]
  fft_staged(x)    — stage-at-a-time via the BU-array kernel [column-arch baseline]
  fft2_kernel(x)   — fused 2D FFT (row+turn+column in VMEM)  [beyond-paper fusion]
  rfft_kernel(x)   — real-input 1D FFT, two-for-one packing  [half traffic]
  rfft2_kernel(x)  — real-input fused 2D FFT                 [half traffic]

All fused entry points take ``radix`` (2 or 4): radix-4 halves the in-VMEM
stage count and the twiddle transcendentals. 2D entry points fail over to an
unfused row/column composition when the frame's true working set exceeds the
VMEM budget (``fft2_fits_vmem``) instead of overflowing it.

Each entry point's fused branch is one compiled program with a stable name
(``repro_rfft2_kernel``, ...): the input cast or re/im split, the fused
Pallas call and the complex64 result built from its re/im planes, so no
eager program splits or assembles a complex array around the kernel.
``fft2_kernel`` and ``rfft2_kernel`` dispatch it under a ``repro.obs`` span
``kernel.launch``; their failover dispatches its stages separately, under
``fft.rows`` and ``fft.columns`` (and ``fft2_kernel``'s complex result
after them under ``kernel.assemble``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental.layout import Layout, with_layout_constraint

from repro import obs
from repro.core.fft1d import bit_reversal_permutation
from repro.resilience import faults as _faults
from repro.kernels.butterfly import butterfly_stage
from repro.kernels.fft_radix2 import (
    _FFT2_WORKING_ARRAYS,
    _VMEM_BUDGET_BYTES,
    fft2_fits_vmem,
    fft2_fused,
    fft_fits_vmem,
    fft_fused,
    irfft2_fused,
    irfft_fused,
    rfft2_fused,
    rfft_fused,
)

__all__ = [
    "fft_kernel",
    "fft_staged",
    "fft2_kernel",
    "rfft_kernel",
    "irfft_kernel",
    "rfft2_kernel",
    "irfft2_kernel",
    "hbm_traffic_model",
    "fft2_working_set",
    "fft2_fits_budget",
    "vmem_budget_bytes",
]

#: f32 frame-sized arrays live at the real-input fused 2D kernels' peak —
#: fewer than the complex census (``_FFT2_WORKING_ARRAYS``) because the
#: input is one f32 pane, not re+im, and the packed panel is half-width.
#: This is the same count the rfft2/irfft2 failover guards below pass to
#: ``fft2_fits_vmem(..., arrays=6)``.
_REAL2D_ARRAYS = 6


def vmem_budget_bytes() -> int:
    """The VMEM byte budget the fused kernels tile against (one number for
    the whole repo: kernels, planner and imaging all size against it)."""
    return _VMEM_BUDGET_BYTES


def fft2_working_set(h: int, w: int, *, real: bool = False) -> int:
    """True VMEM working set (bytes) of one fused 2D transform of (H, W).

    The public spelling of the kernel census: input/output/working panes
    plus corner-turn temporaries, all f32 frame-sized. Pair it with
    :func:`vmem_budget_bytes` to report or reason about tile headroom
    (``benchmarks/imaging_bench.py`` does); callers that only need the
    yes/no answer use :func:`fft2_fits_budget`, the exact predicate the
    kernel entry points and the ``oaconv2d`` tile planner dispatch on.
    """
    return h * w * 4 * (_REAL2D_ARRAYS if real else _FFT2_WORKING_ARRAYS)


def fft2_fits_budget(h: int, w: int, *, real: bool = False) -> bool:
    """True when a fused 2D transform of (H, W) stays inside the budget —
    the same predicate the kernel entry points fail over on."""
    return fft2_fits_vmem(
        h, w, arrays=_REAL2D_ARRAYS if real else _FFT2_WORKING_ARRAYS
    )


def _interpret_default() -> bool:
    """Compiled on the TPU, interpreted on the CPU (the tests); no other
    backend may quietly interpret the kernels."""
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(
            f"the fused FFT kernels run compiled on 'tpu' or interpreted on "
            f"'cpu'; backend {backend!r} is neither"
        )
    return backend == "cpu"


def _failover_event(kind: str, h: int, w: int, frames: int, *, real: bool) -> None:
    """Record one fused->unfused VMEM failover (the decision was silent
    before: a frame over budget quietly paid three HBM round trips instead
    of one). Emitted at trace time — once per compiled shape, which is
    exactly the granularity the decision is made at."""
    obs.emit(
        "kernel.failover",
        kind=kind,
        shape=(h, w),
        frames=frames,
        working_set=fft2_working_set(h, w, real=real),
        budget=vmem_budget_bytes(),
    )


def _split(x: jax.Array):
    x = jnp.asarray(x)
    if jnp.issubdtype(x.dtype, jnp.complexfloating):
        return jnp.real(x).astype(jnp.float32), jnp.imag(x).astype(jnp.float32)
    return x.astype(jnp.float32), jnp.zeros_like(x, dtype=jnp.float32)


def _flatten_rows(x: jax.Array):
    lead = x.shape[:-1]
    n = x.shape[-1]
    flat = math.prod(lead) if lead else 1  # static shapes: stays trace-safe
    return x.reshape(flat, n), lead


_program = functools.partial(jax.jit, static_argnames=("radix", "interpret"))


def _half_spectrum(yr: jax.Array, yi: jax.Array) -> jax.Array:
    """complex64 from a real-input kernel's (..., N/2+1) re/im planes.

    The kernel writes each plane with its odd last axis padded to whole
    128-lane vregs. The TPU lays the complex64 result out with that axis
    major, padding-free (for batches of whole sublane tiles), and its
    ``X64Combine`` writes in the layout of its operands: so move the
    planes to that layout first, and the combine writes the result in
    place. Left to the compiler, the combine runs on the padded planes
    and the complex64 array is copied after it, a third more bytes and
    one more pass (measured on a v5e at 2048x512x512).
    """
    major = Layout(major_to_minor=(yr.ndim - 1, *range(yr.ndim - 1)))
    return lax.complex(with_layout_constraint(yr, major), with_layout_constraint(yi, major))


@_program
def repro_fft_kernel(x: jax.Array, *, radix: int, interpret: bool) -> jax.Array:
    """:func:`fft_kernel` as one program: split, fused kernel, complex64."""
    re, im = _split(x)
    re2, lead = _flatten_rows(re)
    im2, _ = _flatten_rows(im)
    yr, yi = fft_fused(re2, im2, radix=radix, interpret=interpret)
    return lax.complex(yr, yi).reshape(*lead, x.shape[-1])


def fft_kernel(x: jax.Array, *, radix: int = 2, interpret: bool | None = None) -> jax.Array:
    """Fused-kernel FFT along the last axis (any leading batch dims)."""
    interpret = _interpret_default() if interpret is None else interpret
    return repro_fft_kernel(x, radix=radix, interpret=interpret)


def fft_staged(x: jax.Array, *, interpret: bool | None = None) -> jax.Array:
    """Stage-at-a-time FFT: log2(N) kernel launches, log2(N) HBM round trips."""
    interpret = _interpret_default() if interpret is None else interpret
    re, im = _split(x)
    re2, lead = _flatten_rows(re)
    im2, _ = _flatten_rows(im)
    n = re2.shape[-1]
    rev = jnp.asarray(bit_reversal_permutation(n))
    re2 = jnp.take(re2, rev, axis=-1)
    im2 = jnp.take(im2, rev, axis=-1)
    for s in range(int(math.log2(n))):  # the control unit's stage counter
        re2, im2 = butterfly_stage(re2, im2, stage=s, interpret=interpret)
    y = re2 + 1j * im2
    return y.reshape(*lead, n)


def _frames(x: jax.Array):
    h, w = x.shape[-2], x.shape[-1]
    lead = x.shape[:-2]
    f = 1
    for d in lead:
        f *= d
    return f, h, w, lead


def _jnp_variant(radix: int) -> str:
    return "radix4" if radix == 4 else "stockham"


def _fft_rows(re: jax.Array, im: jax.Array, *, radix: int, interpret: bool):
    """Last-axis complex FFT for the 2D failover paths: the fused kernel
    when a row tile fits VMEM, the jnp engine otherwise — the failover
    never overflows, whatever the frame geometry."""
    if fft_fits_vmem(re.shape[-1]):
        return fft_fused(re, im, radix=radix, interpret=interpret)
    from repro.core.fft1d import fft_impl  # lazy: core imports kernels

    z = fft_impl(re + 1j * im, variant=_jnp_variant(radix))
    return jnp.real(z).astype(jnp.float32), jnp.imag(z).astype(jnp.float32)


@_program
def repro_fft2_kernel(x: jax.Array, *, radix: int, interpret: bool) -> jax.Array:
    """The fused branch of :func:`fft2_kernel` as one program."""
    re, im = _split(x)
    f, h, w, lead = _frames(x)
    yr, yi = fft2_fused(re.reshape(f, h, w), im.reshape(f, h, w), radix=radix,
                        interpret=interpret)
    return lax.complex(yr, yi).reshape(*lead, h, w)


def fft2_kernel(x: jax.Array, *, radix: int = 2, interpret: bool | None = None) -> jax.Array:
    """Fused-kernel 2D FFT of (..., H, W); unfused failover for big frames."""
    interpret = _interpret_default() if interpret is None else interpret
    x = jnp.asarray(x)
    f, h, w, lead = _frames(x)
    if fft2_fits_vmem(h, w) and not _faults.vmem_exhausted(
        "kernel.fused", kind="fft2d", h=h, w=w
    ):
        with obs.span("kernel.launch", kernel="repro_fft2_fused"):
            return repro_fft2_kernel(x, radix=radix, interpret=interpret)
    else:
        # Frame working set exceeds VMEM: row pass, materialised corner
        # turn, column pass — more HBM trips, but never an overflow.
        _failover_event("fft2d", h, w, f, real=False)
        re, im = _split(x)
        re, im = re.reshape(f, h, w), im.reshape(f, h, w)
        with obs.span("fft.rows"):
            yr, yi = _fft_rows(re.reshape(f * h, w), im.reshape(f * h, w),
                               radix=radix, interpret=interpret)
        with obs.span("fft.columns"):
            yr = yr.reshape(f, h, w).swapaxes(-1, -2).reshape(f * w, h)
            yi = yi.reshape(f, h, w).swapaxes(-1, -2).reshape(f * w, h)
            yr, yi = _fft_rows(yr, yi, radix=radix, interpret=interpret)
            yr = yr.reshape(f, w, h).swapaxes(-1, -2)
            yi = yi.reshape(f, w, h).swapaxes(-1, -2)
    with obs.span("kernel.assemble"):
        return (yr + 1j * yi).reshape(*lead, h, w)


@_program
def repro_rfft_kernel(x: jax.Array, *, radix: int, interpret: bool) -> jax.Array:
    """:func:`rfft_kernel` as one program: cast, fused kernel, complex64."""
    re, lead = _flatten_rows(x.astype(jnp.float32))
    yr, yi = rfft_fused(re, radix=radix, interpret=interpret)
    return _half_spectrum(yr, yi).reshape(*lead, x.shape[-1] // 2 + 1)


def rfft_kernel(x: jax.Array, *, radix: int = 2, interpret: bool | None = None) -> jax.Array:
    """Real-input fused FFT along the last axis -> (..., N/2+1) complex."""
    interpret = _interpret_default() if interpret is None else interpret
    return repro_rfft_kernel(x, radix=radix, interpret=interpret)


@_program
def repro_irfft_kernel(y: jax.Array, *, radix: int, interpret: bool) -> jax.Array:
    """:func:`irfft_kernel` as one program: split, fused kernel."""
    re, im = _split(y)
    re2, lead = _flatten_rows(re)
    im2, _ = _flatten_rows(im)
    out = irfft_fused(re2, im2, radix=radix, interpret=interpret)
    return out.reshape(*lead, out.shape[-1])


def irfft_kernel(y: jax.Array, *, radix: int = 2, interpret: bool | None = None) -> jax.Array:
    """Inverse of :func:`rfft_kernel`: (..., N/2+1) complex -> real (..., N)."""
    interpret = _interpret_default() if interpret is None else interpret
    return repro_irfft_kernel(y, radix=radix, interpret=interpret)


@_program
def repro_rfft2_kernel(x: jax.Array, *, radix: int, interpret: bool) -> jax.Array:
    """The fused branch of :func:`rfft2_kernel` as one program."""
    f, h, w, lead = _frames(x)
    yr, yi = rfft2_fused(x.astype(jnp.float32).reshape(f, h, w), radix=radix,
                         interpret=interpret)
    return _half_spectrum(yr, yi).reshape(*lead, h, w // 2 + 1)


def rfft2_kernel(x: jax.Array, *, radix: int = 2, interpret: bool | None = None) -> jax.Array:
    """Real-input fused 2D FFT of (..., H, W) -> (..., H, W/2+1) complex."""
    interpret = _interpret_default() if interpret is None else interpret
    x = jnp.asarray(x)
    f, h, w, lead = _frames(x)
    if fft2_fits_vmem(h, w, arrays=_REAL2D_ARRAYS) and not _faults.vmem_exhausted(
        "kernel.fused", kind="rfft2d", h=h, w=w
    ):
        with obs.span("kernel.launch", kernel="repro_rfft2_fused"):
            return repro_rfft2_kernel(x, radix=radix, interpret=interpret)
    else:
        # Unfused failover: row rfft kernel, corner turn in HBM, column FFT.
        # The column batch (f·(W/2+1) rows) is odd, which would force the
        # fused kernel to a degenerate 1-row tile — the jnp engine handles
        # that pass instead.
        _failover_event("rfft2d", h, w, f, real=True)
        from repro.core.fft1d import fft_impl  # lazy: core imports kernels

        xf = x.astype(jnp.float32).reshape(f, h, w)
        half = w // 2 + 1
        with obs.span("fft.rows"):
            if fft_fits_vmem(w):
                yr, yi = rfft_fused(xf.reshape(f * h, w), radix=radix,
                                    interpret=interpret)
                z = (yr + 1j * yi).reshape(f, h, half)
            else:
                from repro.core.rfft import rfft_impl  # rows too long for any tile

                z = rfft_impl(xf.reshape(f * h, w), variant=_jnp_variant(radix))
                z = z.reshape(f, h, half)
        with obs.span("fft.columns"):
            z = fft_impl(z.swapaxes(-1, -2), variant=_jnp_variant(radix))
            z = z.swapaxes(-1, -2)
            return z.reshape(*lead, h, half)


@_program
def repro_irfft2_kernel(y: jax.Array, *, radix: int, interpret: bool) -> jax.Array:
    """The fused branch of :func:`irfft2_kernel` as one program."""
    re, im = _split(y)
    f, h, half, lead = _frames(y)
    out = irfft2_fused(re.reshape(f, h, half), im.reshape(f, h, half), radix=radix,
                       interpret=interpret)
    return out.reshape(*lead, h, 2 * (half - 1))


def irfft2_kernel(y: jax.Array, *, radix: int = 2, interpret: bool | None = None) -> jax.Array:
    """Inverse of :func:`rfft2_kernel`: (..., H, W/2+1) -> real (..., H, W)."""
    interpret = _interpret_default() if interpret is None else interpret
    y = jnp.asarray(y)
    f, h, half, lead = _frames(y)
    w = 2 * (half - 1)
    if fft2_fits_vmem(h, w, arrays=_REAL2D_ARRAYS) and not _faults.vmem_exhausted(
        "kernel.fused", kind="irfft2d", h=h, w=w
    ):
        return repro_irfft2_kernel(y, radix=radix, interpret=interpret)
    else:
        # Column IFFT via the jnp engine (the odd f·(W/2+1) column batch
        # defeats the fused kernel's row tiling), then the fused row irfft.
        _failover_event("irfft2d", h, w, f, real=True)
        re, im = _split(y)
        re, im = re.reshape(f, h, half), im.reshape(f, h, half)
        from repro.core.fft1d import ifft_impl  # lazy: core imports kernels

        z = ifft_impl((re + 1j * im).swapaxes(-1, -2), variant=_jnp_variant(radix))
        z = z.swapaxes(-1, -2)
        if fft_fits_vmem(w):
            fr = jnp.real(z).astype(jnp.float32).reshape(f * h, half)
            fi = jnp.imag(z).astype(jnp.float32).reshape(f * h, half)
            out = irfft_fused(fr, fi, radix=radix, interpret=interpret)
        else:
            from repro.core.rfft import irfft_impl  # rows too long for any tile

            out = irfft_impl(z.reshape(f * h, half), variant=_jnp_variant(radix))
        out = out.reshape(f, h, w)
    return out.reshape(*lead, h, w)


def hbm_traffic_model(
    batch: int, n: int, fused: bool, *, radix: int = 2, real: bool = False
) -> int:
    """Bytes moved between HBM and VMEM (re+im f32, read+write per pass).

    fused: one round trip. staged: one per stage — the paper's α = 1/log2 N
    shows up as traffic(fused)/traffic(staged). ``radix=4`` halves the pass
    count of the staged path (4-point butterflies); ``real`` halves every
    pass (N real samples in, N/2+1 complex bins out — the two-for-one pack).
    """
    stages = int(math.log2(n))
    passes = 1 if fused else math.ceil(stages / math.log2(radix))
    per_pass = batch * n * 4 * 2 * 2
    if real:
        per_pass //= 2
    return passes * per_pass
