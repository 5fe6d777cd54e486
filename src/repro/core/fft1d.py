"""Iterative radix-2 FFT with butterfly-unit reuse (the paper's 1D engine).

The paper's 1D FFT processor instantiates only N/2 butterfly units and reuses
them for log2(N) stages, steered by a control unit (Stage Bus), a routing
network (stage-dependent shuffle) and a register array (feedback path).

JAX mapping (see DESIGN.md §2):

  * ``variant="looped"``   — paper-faithful: one stage body inside
    ``lax.fori_loop``; the induction variable is the Stage Bus, per-stage
    routing/twiddle tables are the routing network + twiddle ROM, and the loop
    carry is the register array.
  * ``variant="unrolled"`` — the "array architecture" baseline the paper
    compares against: log2(N) stage bodies laid out in space (XLA sees
    log2(N) separate stage computations).
  * ``variant="stockham"`` — beyond-paper optimized variant: Stockham
    autosort (no bit-reversal gather, contiguous reshapes only) — the
    TPU-friendliest access pattern; used by the optimized kernels.
  * ``variant="radix4"``   — radix-4 Stockham: half the stage count and
    half the twiddle transcendentals (one radix-2 stage when log2(N) is
    odd) — the software analogue of the higher-radix butterfly papers.

All variants compute the same DFT and are tested against each other and a
float64 DFT oracle. Each 1D pass, the cast, the axis moves and (inverse)
the conjugations and scaling included, is one compiled program per shape
(``repro_jnp_fft_pass``).

This module is a library of transform bodies: it takes a concrete variant
and knows nothing of planning or of the engine registry, which binds each
variant to its engine name. The fused Pallas kernels (engines ``fused``
and ``fused_r4``) live in ``repro.kernels``.
"""

from __future__ import annotations

import functools
import math
from typing import Literal, Optional

import jax
import jax.numpy as jnp
import numpy as np

Variant = Literal["looped", "unrolled", "stockham", "radix4"]

__all__ = [
    "fft_impl",
    "ifft_impl",
    "fft_routing_tables",
    "bit_reversal_permutation",
    "butterfly_counts",
]


def _check_pow2(n: int, axis: Optional[int] = None) -> int:
    """log2(n), or a ValueError that names the offending axis and size.

    The one pow2 error contract for the whole stack: the front door and
    the transform bodies both validate through here, so the message can
    never drift between layers.
    """
    if n < 2 or (n & (n - 1)) != 0:
        if axis is not None:
            raise ValueError(
                f"axis {axis} has length {n}; xfft requires a power of "
                "two >= 2"
            )
        raise ValueError(f"radix-2 FFT needs a power-of-two length, got {n}")
    return int(math.log2(n))


def _next_pow2(n: int) -> int:
    """Power-of-two cover of ``n``, floored at 2 (the engines' minimum
    transform length). Shared by fftconv, the imaging tiled-convolution
    padding and the planner's oaconv2d tile sweep."""
    return max(2, 1 << max(int(n) - 1, 0).bit_length())


def canonical_axis(axis: int, ndim: int, name: str = "fft") -> int:
    """Normalize ``axis`` into [0, ndim), naming the axis in the error."""
    if not -ndim <= axis < ndim:
        raise ValueError(
            f"{name}: axis {axis} is out of bounds for an array of "
            f"dimension {ndim}"
        )
    return axis % ndim


@functools.lru_cache(maxsize=64)
def bit_reversal_permutation(n: int) -> np.ndarray:
    """Index permutation that bit-reverses ``n`` positions (DIT input order)."""
    bits = _check_pow2(n)
    idx = np.arange(n, dtype=np.int64)
    rev = np.zeros(n, dtype=np.int64)
    for b in range(bits):
        rev |= ((idx >> b) & 1) << (bits - 1 - b)
    return rev


@functools.lru_cache(maxsize=64)
def fft_routing_tables(n: int):
    """Per-stage routing network + twiddle ROM for the looped engine.

    Returns numpy arrays, all indexed by stage ``s`` (the Stage Bus value):
      idx_a   (L, N/2) int32 — "odd"/top input index of each butterfly unit
      idx_b   (L, N/2) int32 — "even"/bottom input index (= idx_a + half)
      twiddle (L, N/2) c64   — W_m^p per butterfly unit
      unperm  (L, N)   int32 — inverse shuffle: position i of the stage output
                               gathers from concat([top_out, bot_out])[unperm[i]]

    The paper's routing network shuffles register-array contents per stage as
    a function of SB; these tables are that shuffle, precomputed.
    """
    stages = _check_pow2(n)
    half_n = n // 2
    idx_a = np.zeros((stages, half_n), dtype=np.int32)
    idx_b = np.zeros((stages, half_n), dtype=np.int32)
    twiddle = np.zeros((stages, half_n), dtype=np.complex64)
    unperm = np.zeros((stages, n), dtype=np.int32)
    for s in range(stages):
        half = 1 << s          # butterfly span within a block
        m = half * 2           # block size at this stage
        j = 0
        pos_of = np.zeros(n, dtype=np.int32)
        for blk in range(0, n, m):
            for p in range(half):
                a = blk + p
                b = a + half
                idx_a[s, j] = a
                idx_b[s, j] = b
                twiddle[s, j] = np.exp(-2j * np.pi * p / m).astype(np.complex64)
                pos_of[a] = j           # top output j lives at position a
                pos_of[b] = half_n + j  # bottom output j lives at position b
                j += 1
        unperm[s] = pos_of
    return idx_a, idx_b, twiddle, unperm


def butterfly_counts(n: int, proposed: bool) -> dict:
    """Analytic resource counts from the paper (Tables 1 & 2), 1D engine."""
    stages = _check_pow2(n)
    bu = n // 2 if proposed else (n // 2) * stages
    return {
        "butterfly_units": bu,
        "multipliers": bu,
        "adders_subtractors": 2 * bu,
        "stages": stages,
    }


def _stage_tables_device(n: int):
    idx_a, idx_b, tw, unperm = fft_routing_tables(n)
    return (
        jnp.asarray(idx_a),
        jnp.asarray(idx_b),
        jnp.asarray(tw),
        jnp.asarray(unperm),
    )


def _butterfly_stage(x, idx_a, idx_b, tw):
    """One pass through the N/2 butterfly units (paper fig. 6a).

    top = A + W·B ; bot = A − W·B, computed for all N/2 units at once.
    """
    a = jnp.take(x, idx_a, axis=-1)
    b = jnp.take(x, idx_b, axis=-1) * tw
    return a + b, a - b


def _fft_looped(x: jax.Array, n: int) -> jax.Array:
    """Paper-faithful engine: N/2 butterflies reused log2(N) times.

    fori_loop induction variable == Stage Bus; carry == register array.
    """
    stages = _check_pow2(n)
    idx_a, idx_b, tw, unperm = _stage_tables_device(n)
    x = jnp.take(x, jnp.asarray(bit_reversal_permutation(n)), axis=-1)

    def stage_body(s, regs):
        top, bot = _butterfly_stage(regs, idx_a[s], idx_b[s], tw[s])
        merged = jnp.concatenate([top, bot], axis=-1)
        return jnp.take(merged, unperm[s], axis=-1)

    return jax.lax.fori_loop(0, stages, stage_body, x)


def _fft_unrolled(x: jax.Array, n: int) -> jax.Array:
    """Array-architecture baseline: stages laid out in space (Python loop)."""
    stages = _check_pow2(n)
    idx_a, idx_b, tw, unperm = _stage_tables_device(n)
    x = jnp.take(x, jnp.asarray(bit_reversal_permutation(n)), axis=-1)
    for s in range(stages):
        top, bot = _butterfly_stage(x, idx_a[s], idx_b[s], tw[s])
        merged = jnp.concatenate([top, bot], axis=-1)
        x = jnp.take(merged, unperm[s], axis=-1)
    return x


@functools.lru_cache(maxsize=64)
def _stockham_twiddles(n: int):
    """Per-stage twiddles for the Stockham autosort schedule."""
    stages = _check_pow2(n)
    out = []
    for s in range(stages):
        l = 1 << s  # current transform length of each sub-FFT
        k = np.arange(l, dtype=np.float64)
        out.append(np.exp(-2j * np.pi * k / (2 * l)).astype(np.complex64))
    return tuple(out)


def _fft_stockham(x: jax.Array, n: int) -> jax.Array:
    """Stockham autosort: no bit-reversal, contiguous strides (TPU-friendly)."""
    stages = _check_pow2(n)
    tws = _stockham_twiddles(n)
    batch = x.shape[:-1]
    # y has shape (..., r, l): r sub-FFTs each of length l = n/r.
    y = x.reshape(*batch, n, 1)
    for s in range(stages):
        l = 1 << s
        r = n >> (s + 1)  # half the current number of sub-sequences
        tw = jnp.asarray(tws[s])  # (l,)
        y = y.reshape(*batch, 2, r, l)
        a = y[..., 0, :, :]
        b = y[..., 1, :, :] * tw
        y = jnp.concatenate([a + b, a - b], axis=-1)  # (..., r, 2l)
    return y.reshape(*batch, n)


@functools.lru_cache(maxsize=64)
def _radix4_twiddles(n: int):
    """Per-radix-4-stage base twiddles W_{4l}^k (W^2, W^3 are derived)."""
    stages = _check_pow2(n)
    out = []
    l = 2 if stages % 2 else 1
    while l < n:
        k = np.arange(l, dtype=np.float64)
        out.append(np.exp(-2j * np.pi * k / (4 * l)).astype(np.complex64))
        l *= 4
    return tuple(out)


def _fft_radix4(x: jax.Array, n: int) -> jax.Array:
    """Radix-4 Stockham autosort: ceil(log2(N)/2) stages of 4-point
    butterflies — half the stage shuffles and half the twiddle tables of the
    radix-2 schedule (one twiddle-free radix-2 stage when log2(N) is odd)."""
    stages = _check_pow2(n)
    batch = x.shape[:-1]
    y = x.reshape(*batch, n, 1)
    l = 1
    if stages % 2:
        r = n >> 1
        y = y.reshape(*batch, 2, r, 1)
        a = y[..., 0, :, :]
        b = y[..., 1, :, :]
        y = jnp.concatenate([a + b, a - b], axis=-1)
        l = 2
    for w1_np in _radix4_twiddles(n):
        r = n // (4 * l)
        y = y.reshape(*batch, 4, r, l)
        w1 = jnp.asarray(w1_np)
        w2 = w1 * w1
        w3 = w2 * w1
        a0 = y[..., 0, :, :]
        a1 = y[..., 1, :, :] * w1
        a2 = y[..., 2, :, :] * w2
        a3 = y[..., 3, :, :] * w3
        s02, d02 = a0 + a2, a0 - a2
        s13, d13 = a1 + a3, a1 - a3
        # X[k+c'l] = sum_j (-i)^(j c') a_j W^(jk): the ±i are free rotations.
        y = jnp.concatenate(
            [s02 + s13, d02 - 1j * d13, s02 - s13, d02 + 1j * d13], axis=-1
        )
        l *= 4
    return y.reshape(*batch, n)


#: The jnp engines: complex64 FFT along the last axis, one body per variant.
_JNP_BODIES = {
    "looped": _fft_looped,
    "unrolled": _fft_unrolled,
    "stockham": _fft_stockham,
    "radix4": _fft_radix4,
}


def _fft_jnp(x: jax.Array, variant: str) -> jax.Array:
    """Forward jnp engine along the last axis (complex64 in and out)."""
    return _JNP_BODIES[variant](x, x.shape[-1])


def _ifft_jnp(x: jax.Array, variant: str) -> jax.Array:
    """Inverse jnp engine along the last axis via the conjugation identity."""
    return jnp.conj(_fft_jnp(jnp.conj(x), variant)) / x.shape[-1]


@functools.partial(jax.jit, static_argnames=("axis", "variant", "inverse"))
def repro_jnp_fft_pass(
    x: jax.Array, axis: int, variant: str, inverse: bool
) -> jax.Array:
    """One complex pass of a jnp engine along ``axis`` as one program: the
    complex64 cast, the move to the last axis and back, and the stages."""
    x = jnp.moveaxis(x.astype(jnp.complex64), axis, -1)
    y = _ifft_jnp(x, variant) if inverse else _fft_jnp(x, variant)
    return jnp.moveaxis(y, -1, axis)


def fft_impl(x: jax.Array, axis: int = -1, *, variant: Variant) -> jax.Array:
    """FFT along ``axis`` on jnp engine ``variant``: real or complex in,
    complex64 out, one pass program."""
    x = jnp.asarray(x)
    return repro_jnp_fft_pass(x, axis=canonical_axis(axis, x.ndim), variant=variant,
                              inverse=False)


def ifft_impl(x: jax.Array, axis: int = -1, *, variant: Variant) -> jax.Array:
    """Inverse FFT along ``axis`` (1/N) on jnp engine ``variant``, one pass
    program."""
    x = jnp.asarray(x)
    return repro_jnp_fft_pass(x, axis=canonical_axis(axis, x.ndim), variant=variant,
                              inverse=True)
