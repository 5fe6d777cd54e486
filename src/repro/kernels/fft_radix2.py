"""Fused radix-2/radix-4 FFT Pallas kernels — the paper's reuse insight, TPU-native.

The paper keeps ONE stage of butterfly hardware and streams all log2(N)
stages through it. The TPU translation (DESIGN.md §2): keep the data panel
resident in VMEM and stream all stages over it inside one kernel — one HBM
read + one HBM write for the whole transform, instead of the log2(N) round
trips of the stage-at-a-time baseline (`kernels/butterfly.py`). The paper's
area reduction factor (1/log2 N, eq. 5) reappears as the HBM traffic ratio
between the two kernels.

Two in-VMEM schedules, both Stockham autosort (contiguous reshapes, no
bit-reversal gather — TPU vector units hate gathers):

  * radix-2 (``_stockham_panel``)    — log2(N) stages of 2-point butterflies.
  * radix-4 (``_stockham_panel_r4``) — log4(N) stages of 4-point butterflies
    (one leading radix-2 stage when log2(N) is odd): half the stage count
    and half the stage shuffles.

Layout: every panel transforms along AXIS 0 of an (N, L) plane. The
transform axis lives on sublanes and the leading (untiled) dimension, the L
independent transforms fill the 128 lanes. A Stockham stage is then a
split of the leading dimension plus a stack along it — shape casts the
Mosaic compiler accepts at every stage width — and each stage's twiddles
are one (l, 1) column evaluated from an integer iota (the twiddle "ROM":
generated in-kernel, costing no HBM) and broadcast across lanes. Row
transforms reach this layout through the in-VMEM transpose (the XLU
corner turn); lane-strided slices, ``rev`` and cross-vreg gathers, which
the chip's compiler refuses, never appear. Rows too long to turn 128 of
them inside the budget are held as (N/128, 128) planes instead and run a
four-step split: FFT down the columns, twiddle, turn, FFT along the 128.

Real-input kernels (two-for-one Hermitian packing): ``rfft_fused`` packs N
reals as N/2 complex, runs the half-size panel, and untangles the spectrum
with the conjugate-symmetry recombination — inside the same kernel, so the
whole real transform is still one HBM round trip at half the traffic of the
complex path. ``rfft2_fused``/``irfft2_fused`` fuse the row rfft, the
in-VMEM corner turn and the column FFT the same way.

ABI: separate float32 re/im planes (TPU Pallas has no complex dtype).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "fft_panel_kernel",
    "fft_fused",
    "fft2_fused",
    "fft_fits_vmem",
    "fft1_working_set",
    "fft2_fits_vmem",
    "pick_row_tile",
    "rfft_fused",
    "irfft_fused",
    "rfft2_fused",
    "irfft2_fused",
]

#: Census budget: the bytes of frame- or tile-sized f32 arrays (the counts
#: below) one grid step may keep live. Kernels, planner and imaging all
#: tile against this one number.
_VMEM_BUDGET_BYTES = 8 * 1024 * 1024

#: Scoped-VMEM limit handed to the Mosaic compiler. A kernel at the census
#: budget needs more than the budget itself: the pipeline double-buffers
#: every input and output block, and the compiler keeps the stage values
#: and corner-turn copies in its own scratch, more of it the more stages a
#: panel runs. Compiled for v5e, blocks at the budget need 2.4-5.6x it:
#: 512x512 complex fft2 about 20 MiB, a 128x2048 real row block about
#: 19 MiB, a 256x1024 complex row block about 24 MiB, and the census's most
#: elongated complex frame, 128x2048 at radix 2, 44.5 MiB. All are over the
#: compiler's 16 MiB default; this limit covers them inside v5e's 128 MiB
#: of VMEM per core.
_VMEM_LIMIT_BYTES = 64 * 1024 * 1024

#: f32 arrays of frame size live at the 2D kernel's peak: input re/im panes,
#: output re/im panes, the working panel re/im, and the corner-turn's
#: transposed temporaries re/im. The old guard counted only 4 and let large
#: frames overflow VMEM silently.
_FFT2_WORKING_ARRAYS = 8

#: Same census for the 1D panel: input re/im, output re/im, working re/im.
_FFT1_WORKING_ARRAYS = 6

#: Lanes of a vreg. Panels transform along axis 0 with independent
#: transforms across lanes, so a turned (N, rows) block occupies whole
#: 128-lane vregs however few rows it holds: the census counts every
#: block and every frame dim at no less than this.
_LANES = 128

#: The four-step layout of long rows adds the (N/128, 128) twiddle plane,
#: re and im, to the 1D census.
_SPLIT_TWIDDLE_ARRAYS = 2


def _compiler_params():
    return pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT_BYTES)


def _lanes(x: int) -> int:
    """``x`` rounded up to whole vregs of lanes."""
    return -(-x // _LANES) * _LANES


def _split_rows(n: int) -> bool:
    """True when length-N rows are too long to turn a vreg's worth of them
    (128 rows) inside the budget; such rows take the four-step layout."""
    return _LANES * n * 4 * _FFT1_WORKING_ARRAYS > _VMEM_BUDGET_BYTES


def pick_row_tile(batch: int, n: int, arrays: int = _FFT1_WORKING_ARRAYS) -> int:
    """Rows per block: the whole batch when its working set fits the VMEM
    budget, else the largest power-of-two row count that fits.

    ``arrays`` is the number of f32 row-sized arrays simultaneously live in
    the kernel (inputs + outputs + working copies), not just the I/O count.
    A tile that does not divide ``batch`` is fine: the entry points pad the
    batch up to a multiple of it.
    """
    if _split_rows(n):
        # Rows are whole (N/128, 128) planes on a leading block dim: no
        # lane padding, plus the twiddle plane.
        cap = max(1, _VMEM_BUDGET_BYTES // (n * 4 * (arrays + _SPLIT_TWIDDLE_ARRAYS)))
        return batch if batch <= cap else 1 << (cap.bit_length() - 1)
    cap = max(1, _VMEM_BUDGET_BYTES // max(n * 4 * arrays, 1))
    if _lanes(batch) <= cap:
        return batch
    return 1 << (cap.bit_length() - 1)


def fft1_working_set(n: int) -> int:
    """Census bytes of the smallest block of length-N rows: 128 rows (one
    vreg's worth of lanes once turned), or for rows too long for that, one
    row in the four-step (N/128, 128) layout with its twiddle plane."""
    if _split_rows(n):
        return n * 4 * (_FFT1_WORKING_ARRAYS + _SPLIT_TWIDDLE_ARRAYS)
    return _LANES * n * 4 * _FFT1_WORKING_ARRAYS


def fft_fits_vmem(n: int) -> bool:
    """True when the smallest block of length-N rows fits the budget (past
    this, even one row would overflow VMEM)."""
    return fft1_working_set(n) <= _VMEM_BUDGET_BYTES


def fft2_fits_vmem(h: int, w: int, arrays: int = _FFT2_WORKING_ARRAYS) -> bool:
    """True when a fused 2D kernel's real working set fits the VMEM budget
    (each frame dim is the lane dim of one pass, so it counts in whole
    vregs)."""
    return _lanes(h) * _lanes(w) * 4 * arrays <= _VMEM_BUDGET_BYTES


# --------------------------- in-VMEM panels -------------------------------


def _twiddles(l: int, span: int, sign: float = -1.0):
    """cos/sin of ``sign * 2*pi * k / span`` for k in [0, l), as (l, 1)
    columns that broadcast across the lanes of a stage."""
    k = jax.lax.broadcasted_iota(jnp.int32, (l, 1), 0).astype(jnp.float32)
    ang = (sign * 2.0 * math.pi / span) * k
    return jnp.cos(ang), jnp.sin(ang)


def _stockham_panel(re: jax.Array, im: jax.Array, n: int):
    """All log2(N) radix-2 stages along axis 0 of (N, L) planes, in VMEM."""
    lanes = re.shape[-1]
    yr, yi = re, im
    l = 1
    while l < n:
        r = n // (2 * l)
        yr = yr.reshape(2, r, l, lanes)
        yi = yi.reshape(2, r, l, lanes)
        wr, wi = _twiddles(l, 2 * l)
        ar, ai = yr[0], yi[0]
        br, bi = yr[1], yi[1]
        tr = br * wr - bi * wi
        ti = br * wi + bi * wr
        yr = jnp.stack([ar + tr, ar - tr], axis=1).reshape(n, lanes)
        yi = jnp.stack([ai + ti, ai - ti], axis=1).reshape(n, lanes)
        l *= 2
    return yr, yi


def _stockham_panel_r4(re: jax.Array, im: jax.Array, n: int):
    """Radix-4 Stockham panel along axis 0: log4(N) stages of 4-point
    butterflies.

    Odd log2(N) runs one twiddle-free radix-2 stage first, then radix-4 the
    rest of the way. The ±i rotations of the 4-point butterfly are free
    sign/plane swaps.
    """
    lanes = re.shape[-1]
    yr, yi = re, im
    l = 1
    if n > 1 and int(math.log2(n)) % 2:
        # One radix-2 stage (l=1 -> twiddle-free) to make the rest radix-4.
        yr = yr.reshape(2, n // 2, lanes)
        yi = yi.reshape(2, n // 2, lanes)
        ar, ai = yr[0], yi[0]
        br, bi = yr[1], yi[1]
        yr = jnp.stack([ar + br, ar - br], axis=1).reshape(n, lanes)
        yi = jnp.stack([ai + bi, ai - bi], axis=1).reshape(n, lanes)
        l = 2
    while l < n:
        r = n // (4 * l)
        yr = yr.reshape(4, r, l, lanes)
        yi = yi.reshape(4, r, l, lanes)
        # W_{4l}^{jk} for j = 1, 2, 3: one cos/sin column, W^2 and W^3 by
        # complex multiplication (no extra transcendentals).
        w1r, w1i = _twiddles(l, 4 * l)
        w2r, w2i = w1r * w1r - w1i * w1i, 2.0 * w1r * w1i
        w3r, w3i = w2r * w1r - w2i * w1i, w2r * w1i + w2i * w1r
        a0r, a0i = yr[0], yi[0]
        a1r = yr[1] * w1r - yi[1] * w1i
        a1i = yr[1] * w1i + yi[1] * w1r
        a2r = yr[2] * w2r - yi[2] * w2i
        a2i = yr[2] * w2i + yi[2] * w2r
        a3r = yr[3] * w3r - yi[3] * w3i
        a3i = yr[3] * w3i + yi[3] * w3r
        s02r, s02i = a0r + a2r, a0i + a2i
        d02r, d02i = a0r - a2r, a0i - a2i
        s13r, s13i = a1r + a3r, a1i + a3i
        d13r, d13i = a1r - a3r, a1i - a3i
        # X[k+c'l] = sum_j (-i)^(j c') a_j: the ±i factors are plane swaps.
        yr = jnp.stack(
            [s02r + s13r, d02r + d13i, s02r - s13r, d02r - d13i], axis=1
        ).reshape(n, lanes)
        yi = jnp.stack(
            [s02i + s13i, d02i - d13r, s02i - s13i, d02i + d13r], axis=1
        ).reshape(n, lanes)
        l *= 4
    return yr, yi


def _panel(radix: int):
    if radix not in (2, 4):
        raise ValueError(f"radix must be 2 or 4, got {radix}")
    return _stockham_panel_r4 if radix == 4 else _stockham_panel


def _reverse_rows(z: jax.Array) -> jax.Array:
    """``z[::-1]`` along axis 0, from whole-vreg picks and one sublane shuffle
    (``rev`` has no TPU lowering)."""
    m, lanes = z.shape
    if m % 8:
        return jnp.concatenate([z[i:i + 1] for i in range(m - 1, -1, -1)], axis=0)
    z3 = z.reshape(m // 8, 8, lanes)
    if m > 8:
        z3 = jnp.concatenate([z3[i:i + 1] for i in range(m // 8 - 1, -1, -1)], axis=0)
    z3 = jnp.concatenate([z3[:, s:s + 1] for s in range(7, -1, -1)], axis=1)
    return z3.reshape(m, lanes)


# ----------------------- real-input (two-for-one) panels -------------------


def _rfft_panel(x: jax.Array, n: int, radix: int):
    """Real (N, L) panel -> half spectrum (N/2+1, L) re/im, along axis 0.

    Classic two-for-one: pack even/odd samples as N/2 complex, run the
    half-size panel, untangle with the Hermitian-symmetry recombination
    Y[k] = Xe[k] + W_N^k Xo[k].
    """
    m = n // 2
    lanes = x.shape[-1]
    pairs = x.reshape(m, 2, lanes)
    zr, zi = _panel(radix)(pairs[:, 0], pairs[:, 1], m)
    # Z[k] for k = 0..M (Z[M] = Z[0]) and conj(Z[(M-k) mod M]).
    zkr = jnp.concatenate([zr, zr[:1]], axis=0)
    zki = jnp.concatenate([zi, zi[:1]], axis=0)
    zmkr = jnp.concatenate([zr[:1], _reverse_rows(zr)], axis=0)
    zmki = -jnp.concatenate([zi[:1], _reverse_rows(zi)], axis=0)
    xer = 0.5 * (zkr + zmkr)
    xei = 0.5 * (zki + zmki)
    dr = zkr - zmkr
    di = zki - zmki
    xor_ = 0.5 * di          # Xo = -i/2 (Zk - conj(Zmk))
    xoi = -0.5 * dr
    wr, wi = _twiddles(m + 1, n)
    yr = xer + wr * xor_ - wi * xoi
    yi = xei + wr * xoi + wi * xor_
    return yr, yi


def _irfft_panel(yr: jax.Array, yi: jax.Array, n: int, radix: int):
    """Half spectrum (N/2+1, L) re/im -> real (N, L) panel (inverse), along
    axis 0."""
    m = n // 2
    lanes = yr.shape[-1]
    # np.fft.irfft semantics: the DC and Nyquist bins of a Hermitian
    # spectrum are real — discard any imaginary part instead of folding
    # it into the output.
    edge = jax.lax.broadcasted_iota(jnp.int32, (m + 1, 1), 0)
    yi = jnp.where((edge == 0) | (edge == m), 0.0, yi)
    ykr, yki = yr[:m], yi[:m]
    # conj(Y[M-k]) for k = 0..M-1 is the reversed tail of the half spectrum.
    ymkr = _reverse_rows(yr[1:])
    ymki = -_reverse_rows(yi[1:])
    xer = 0.5 * (ykr + ymkr)
    xei = 0.5 * (yki + ymki)
    txr = 0.5 * (ykr - ymkr)   # W^k Xo[k]
    txi = 0.5 * (yki - ymki)
    wr, wi = _twiddles(m, n, sign=1.0)   # W^{-k} undoes the forward phase
    xor_ = txr * wr - txi * wi
    xoi = txr * wi + txi * wr
    zr = xer - xoi             # Z = Xe + i·Xo
    zi = xei + xor_
    # IFFT_M via the conjugation identity on the shared forward panel.
    fr, fi = _panel(radix)(zr, -zi, m)
    inv = 1.0 / m
    zr, zi = fr * inv, -fi * inv
    # Interleave: x[2j] = Re(z[j]), x[2j+1] = Im(z[j]).
    return jnp.stack([zr, zi], axis=1).reshape(n, lanes)


# ------------------------------ 1D kernels --------------------------------


def _pad_rows(x: jax.Array, tile: int) -> jax.Array:
    """Pad the row count of a (B, N) plane up to a multiple of ``tile``."""
    extra = -x.shape[0] % tile
    return jnp.pad(x, ((0, extra), (0, 0))) if extra else x


def _check_rows(n: int) -> None:
    if not fft_fits_vmem(n):
        raise ValueError(
            f"length-{n} rows exceed the fused-kernel VMEM budget even at "
            f"one row per block; use an unfused variant"
        )


def fft_panel_kernel(re_ref, im_ref, out_re_ref, out_im_ref, *, radix: int = 2):
    """Kernel body: one VMEM-resident (rows, N) block, all stages fused.

    The block is turned so the transform axis runs along sublanes, then
    turned back for the store."""
    n = re_ref.shape[-1]
    yr, yi = _panel(radix)(re_ref[...].T, im_ref[...].T, n)
    out_re_ref[...] = yr.T
    out_im_ref[...] = yi.T


def _fft_split_kernel(re_ref, im_ref, out_re_ref, out_im_ref, *, radix: int):
    """Four-step FFT of long rows, each held as an (N1, 128) plane.

    With x[a, c] = x[128a + c]: a length-N1 FFT down each of the 128
    columns, the twiddle W_N^{ck}, a turn, and a length-128 FFT along the
    new axis 0. Row k2 of the turned result holds X[k1 + N1*k2] at lane k1,
    so the (128, N1) plane is the spectrum in order."""
    tb, n1, n2 = re_ref.shape
    panel = _panel(radix)
    ck = (jax.lax.broadcasted_iota(jnp.int32, (n1, n2), 0)
          * jax.lax.broadcasted_iota(jnp.int32, (n1, n2), 1))
    ang = (-2.0 * math.pi / (n1 * n2)) * ck.astype(jnp.float32)
    wr, wi = jnp.cos(ang), jnp.sin(ang)

    def row(r, carry):
        yr, yi = panel(re_ref[r], im_ref[r], n1)
        tr = yr * wr - yi * wi
        ti = yr * wi + yi * wr
        yr, yi = panel(tr.T, ti.T, n2)
        out_re_ref[r] = yr
        out_im_ref[r] = yi
        return carry

    jax.lax.fori_loop(0, tb, row, 0)


def _fft_split(re, im, *, tile: int, radix: int, interpret: bool):
    """Long (B, N) rows through :func:`_fft_split_kernel`; the (B, N/128, 128)
    and (B, 128, N/128) views are reshapes done outside the kernel."""
    bp, n = re.shape
    n1 = n // _LANES
    in_spec = pl.BlockSpec((tile, n1, _LANES), lambda i: (i, 0, 0))
    out_spec = pl.BlockSpec((tile, _LANES, n1), lambda i: (i, 0, 0))
    yr, yi = pl.pallas_call(
        functools.partial(_fft_split_kernel, radix=radix),
        grid=(bp // tile,),
        in_specs=[in_spec, in_spec],
        out_specs=[out_spec, out_spec],
        out_shape=[
            jax.ShapeDtypeStruct((bp, _LANES, n1), jnp.float32),
            jax.ShapeDtypeStruct((bp, _LANES, n1), jnp.float32),
        ],
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(re.reshape(bp, n1, _LANES), im.reshape(bp, n1, _LANES))
    return yr.reshape(bp, n), yi.reshape(bp, n)


@functools.partial(jax.jit, static_argnames=("interpret", "row_tile", "radix"))
def fft_fused(
    re: jax.Array,
    im: jax.Array,
    *,
    row_tile: int | None = None,
    radix: int = 2,
    interpret: bool = False,
):
    """FFT along the last axis of (B, N) re/im planes; one kernel pass.

    Rows too long to turn 128 of them in VMEM (``_split_rows``) take the
    four-step (N/128, 128) layout instead of the turned block."""
    b, n = re.shape
    if n & (n - 1):
        raise ValueError(f"power-of-two length required, got {n}")
    _check_rows(n)
    tile = row_tile or pick_row_tile(b, n)
    re, im = _pad_rows(re.astype(jnp.float32), tile), _pad_rows(im.astype(jnp.float32), tile)
    if _split_rows(n):
        yr, yi = _fft_split(re, im, tile=tile, radix=radix, interpret=interpret)
        return yr[:b], yi[:b]
    bp = re.shape[0]
    spec = pl.BlockSpec((tile, n), lambda i: (i, 0))
    yr, yi = pl.pallas_call(
        functools.partial(fft_panel_kernel, radix=radix),
        grid=(bp // tile,),
        in_specs=[spec, spec],
        out_specs=[spec, spec],
        out_shape=[
            jax.ShapeDtypeStruct((bp, n), jnp.float32),
            jax.ShapeDtypeStruct((bp, n), jnp.float32),
        ],
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(re, im)
    return yr[:b], yi[:b]


def _rfft_kernel_body(x_ref, out_re_ref, out_im_ref, *, radix: int):
    yr, yi = _rfft_panel(x_ref[...].T, x_ref.shape[-1], radix)
    out_re_ref[...] = yr.T
    out_im_ref[...] = yi.T


@functools.partial(jax.jit, static_argnames=("interpret", "row_tile", "radix"))
def rfft_fused(
    x: jax.Array,
    *,
    row_tile: int | None = None,
    radix: int = 2,
    interpret: bool = False,
):
    """Real-input FFT of (B, N) -> (B, N/2+1) re/im; one HBM round trip at
    roughly half the complex path's traffic and arithmetic.

    Rows in the four-step layout run the complex kernel at full length on
    (x, 0) and keep the first N/2+1 bins: no two-for-one saving there."""
    b, n = x.shape
    if n < 2 or n & (n - 1):
        raise ValueError(f"power-of-two length >= 2 required, got {n}")
    _check_rows(n)
    m = n // 2
    if _split_rows(n):
        x = x.astype(jnp.float32)
        yr, yi = fft_fused(x, jnp.zeros_like(x), row_tile=row_tile, radix=radix,
                           interpret=interpret)
        return yr[:, :m + 1], yi[:, :m + 1]
    tile = row_tile or pick_row_tile(b, n)
    x = _pad_rows(x.astype(jnp.float32), tile)
    bp = x.shape[0]
    in_spec = pl.BlockSpec((tile, n), lambda i: (i, 0))
    out_spec = pl.BlockSpec((tile, m + 1), lambda i: (i, 0))
    yr, yi = pl.pallas_call(
        functools.partial(_rfft_kernel_body, radix=radix),
        grid=(bp // tile,),
        in_specs=[in_spec],
        out_specs=[out_spec, out_spec],
        out_shape=[
            jax.ShapeDtypeStruct((bp, m + 1), jnp.float32),
            jax.ShapeDtypeStruct((bp, m + 1), jnp.float32),
        ],
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(x)
    return yr[:b], yi[:b]


def _irfft_kernel_body(re_ref, im_ref, out_ref, *, n: int, radix: int):
    out_ref[...] = _irfft_panel(re_ref[...].T, im_ref[...].T, n, radix).T


@functools.partial(jax.jit, static_argnames=("interpret", "row_tile", "radix"))
def irfft_fused(
    re: jax.Array,
    im: jax.Array,
    *,
    row_tile: int | None = None,
    radix: int = 2,
    interpret: bool = False,
):
    """Inverse of :func:`rfft_fused`: (B, N/2+1) re/im -> real (B, N).

    Rows in the four-step layout rebuild the full Hermitian spectrum and
    run the complex kernel at full length."""
    b, half = re.shape
    n = 2 * (half - 1)
    if n < 2 or n & (n - 1):
        raise ValueError(f"half-spectrum width must be N/2+1 with N a power of two, got {half}")
    _check_rows(n)
    if _split_rows(n):
        re, im = re.astype(jnp.float32), im.astype(jnp.float32)
        im = im.at[:, 0].set(0.0).at[:, -1].set(0.0)   # DC and Nyquist are real
        fr = jnp.concatenate([re, re[:, -2:0:-1]], axis=1)
        fi = jnp.concatenate([im, -im[:, -2:0:-1]], axis=1)
        # Re(ifft(Y)) = Re(fft(conj(Y))) / N.
        yr, _ = fft_fused(fr, -fi, row_tile=row_tile, radix=radix, interpret=interpret)
        return yr * (1.0 / n)
    tile = row_tile or pick_row_tile(b, n)
    re, im = _pad_rows(re.astype(jnp.float32), tile), _pad_rows(im.astype(jnp.float32), tile)
    bp = re.shape[0]
    in_spec = pl.BlockSpec((tile, half), lambda i: (i, 0))
    out_spec = pl.BlockSpec((tile, n), lambda i: (i, 0))
    out = pl.pallas_call(
        functools.partial(_irfft_kernel_body, n=n, radix=radix),
        grid=(bp // tile,),
        in_specs=[in_spec, in_spec],
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((bp, n), jnp.float32),
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(re, im)
    return out[:b]


# ------------------------------ 2D kernels --------------------------------


def _fft2_kernel(re_ref, im_ref, out_re_ref, out_im_ref, *, radix: int):
    """Fused 2D FFT: column pass, in-VMEM corner turn, row pass, turn back.

    Beyond-paper fusion: the hardware needs RAM1/RAM2 + a second engine for
    the column pass; with the whole (H, W) frame VMEM-resident both passes
    and the transpose happen on one residency — a single HBM round trip for
    the full 2D transform (vs 2 passes + materialised transpose ≈ 3-4 trips).
    """
    h = re_ref.shape[-2]
    w = re_ref.shape[-1]
    panel = _panel(radix)
    yr, yi = panel(re_ref[0], im_ref[0], h)                      # column pass
    yr, yi = panel(yr.T, yi.T, w)                                # turn, row pass
    out_re_ref[0] = yr.T
    out_im_ref[0] = yi.T


@functools.partial(jax.jit, static_argnames=("interpret", "radix"))
def fft2_fused(
    re: jax.Array, im: jax.Array, *, radix: int = 2, interpret: bool = False
):
    """2D FFT of (F, H, W) frames, one frame per grid step, fully fused."""
    f, h, w = re.shape
    if (h & (h - 1)) or (w & (w - 1)):
        raise ValueError(f"power-of-two frame dims required, got {(h, w)}")
    if not fft2_fits_vmem(h, w):
        # The corner turn materialises transposed temporaries on top of the
        # in/out/working panes; callers should check fft2_fits_vmem() and
        # fail over to the unfused path rather than overflow VMEM.
        raise ValueError(
            f"frame {(h, w)} exceeds the fused-kernel VMEM budget "
            f"({_FFT2_WORKING_ARRAYS} frame-sized arrays live at the corner turn)"
        )
    spec = pl.BlockSpec((1, h, w), lambda i: (i, 0, 0))
    return pl.pallas_call(
        functools.partial(_fft2_kernel, radix=radix),
        grid=(f,),
        in_specs=[spec, spec],
        out_specs=[spec, spec],
        out_shape=[
            jax.ShapeDtypeStruct((f, h, w), jnp.float32),
            jax.ShapeDtypeStruct((f, h, w), jnp.float32),
        ],
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(re.astype(jnp.float32), im.astype(jnp.float32))


def _rfft2_kernel(x_ref, out_re_ref, out_im_ref, *, radix: int):
    """Fused real-input 2D FFT: turn, row rfft, turn back, column FFT."""
    h = x_ref.shape[-2]
    w = x_ref.shape[-1]
    yr, yi = _rfft_panel(x_ref[0].T, w, radix)                   # (W/2+1, H)
    yr, yi = _panel(radix)(yr.T, yi.T, h)                        # column pass
    out_re_ref[0] = yr
    out_im_ref[0] = yi


@functools.partial(jax.jit, static_argnames=("interpret", "radix"))
def rfft2_fused(x: jax.Array, *, radix: int = 2, interpret: bool = False):
    """2D real-input FFT of (F, H, W) -> (F, H, W/2+1) re/im, fully fused."""
    f, h, w = x.shape
    if (h & (h - 1)) or (w & (w - 1)) or w < 2:
        raise ValueError(f"power-of-two frame dims required, got {(h, w)}")
    if not fft2_fits_vmem(h, w, arrays=6):
        raise ValueError(f"frame {(h, w)} exceeds the fused-kernel VMEM budget")
    in_spec = pl.BlockSpec((1, h, w), lambda i: (i, 0, 0))
    out_spec = pl.BlockSpec((1, h, w // 2 + 1), lambda i: (i, 0, 0))
    return pl.pallas_call(
        functools.partial(_rfft2_kernel, radix=radix),
        grid=(f,),
        in_specs=[in_spec],
        out_specs=[out_spec, out_spec],
        out_shape=[
            jax.ShapeDtypeStruct((f, h, w // 2 + 1), jnp.float32),
            jax.ShapeDtypeStruct((f, h, w // 2 + 1), jnp.float32),
        ],
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(x.astype(jnp.float32))


def _irfft2_kernel(re_ref, im_ref, out_ref, *, n: int, radix: int):
    """Inverse fused 2D: column IFFT (conj trick), turn, row irfft, turn back."""
    h = re_ref.shape[-2]
    fr, fi = _panel(radix)(re_ref[0], -im_ref[0], h)             # column IFFT
    inv = 1.0 / h
    yr, yi = (fr * inv).T, (-fi * inv).T                         # (W/2+1, H)
    out_ref[0] = _irfft_panel(yr, yi, n, radix).T                # row irfft


@functools.partial(jax.jit, static_argnames=("interpret", "radix"))
def irfft2_fused(re: jax.Array, im: jax.Array, *, radix: int = 2, interpret: bool = False):
    """Inverse of :func:`rfft2_fused`: (F, H, W/2+1) re/im -> real (F, H, W)."""
    f, h, half = re.shape
    w = 2 * (half - 1)
    if (h & (h - 1)) or w < 2 or (w & (w - 1)):
        raise ValueError(f"bad half-spectrum frame dims {(h, half)}")
    if not fft2_fits_vmem(h, w, arrays=6):
        raise ValueError(f"frame {(h, w)} exceeds the fused-kernel VMEM budget")
    in_spec = pl.BlockSpec((1, h, half), lambda i: (i, 0, 0))
    out_spec = pl.BlockSpec((1, h, w), lambda i: (i, 0, 0))
    return pl.pallas_call(
        functools.partial(_irfft2_kernel, n=w, radix=radix),
        grid=(f,),
        in_specs=[in_spec, in_spec],
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((f, h, w), jnp.float32),
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(re.astype(jnp.float32), im.astype(jnp.float32))
