"""Single-stage butterfly kernel — the paper's N/2-BU array, stage at a time.

One `pallas_call` executes exactly one FFT stage (one pass through the N/2
butterfly units). The paper's *routing network* — the stage-dependent
shuffle between the register array and the BUs — is expressed with ZERO
gathers: at stage s (half-span h = 2^s, block m = 2h) the natural-order
array viewed as (B, N/m, 2, h) puts every butterfly's two inputs in
adjacent sub-rows, so the BlockSpec/reshape IS the routing network.

Running all log2(N) stages through this kernel (``fft_staged`` in ops.py)
is the *column architecture* baseline: the data round-trips HBM log2(N)
times. Compare `fft_radix2.fft_fused` (one round trip) — the measured HBM
traffic ratio reproduces the paper's area ratio α = 1/log2(N).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["butterfly_stage_kernel", "butterfly_stage", "pick_block_tile"]


def butterfly_stage_kernel(re_ref, im_ref, out_re_ref, out_im_ref, *, stage: int):
    """One pass through the BU array for a (TB, G, 2, h) tile.

    A (top) and B (bottom) samples per fig. 6a:  top' = A + W·B, bot' = A − W·B,
    with W = W_{2h}^p generated in-register from an iota over p (twiddle ROM).
    """
    h = re_ref.shape[-1]
    ar, br = re_ref[..., 0, :], re_ref[..., 1, :]
    ai, bi = im_ref[..., 0, :], im_ref[..., 1, :]
    p = jax.lax.broadcasted_iota(jnp.int32, (1, 1, h), 2).astype(jnp.float32)
    ang = (-math.pi / h) * p  # -2π p / m, m = 2h
    wr, wi = jnp.cos(ang), jnp.sin(ang)
    tr = br * wr - bi * wi
    ti = br * wi + bi * wr
    out_re_ref[..., 0, :] = ar + tr
    out_re_ref[..., 1, :] = ar - tr
    out_im_ref[..., 0, :] = ai + ti
    out_im_ref[..., 1, :] = ai - ti


def pick_block_tile(nblk: int, h: int, rows: int) -> tuple[int, int]:
    """(row_tile, group_tile): keep tiles lane-friendly and VMEM-bounded."""
    group = 1
    while group < nblk and group * 2 * h < 1024:
        group *= 2
    while nblk % group:
        group //= 2
    per_row = nblk // max(group, 1) * group * 2 * h * 4 * 4
    row_tile = max(1, min(rows, (4 * 1024 * 1024) // max(per_row, 1)))
    row_tile = 1 << (row_tile.bit_length() - 1)
    while rows % row_tile:
        row_tile //= 2
    return max(row_tile, 1), max(group, 1)


@functools.partial(jax.jit, static_argnames=("stage", "interpret"))
def butterfly_stage(
    re: jax.Array,
    im: jax.Array,
    *,
    stage: int,
    interpret: bool = False,
):
    """Apply DIT stage ``stage`` to (B, N) re/im planes in natural order.

    Input must already be bit-reversed (stage 0) — i.e. this is the engine
    the control unit re-invokes with SB = stage.
    """
    b, n = re.shape
    h = 1 << stage
    m = 2 * h
    nblk = n // m
    re4 = re.reshape(b, nblk, 2, h)
    im4 = im.reshape(b, nblk, 2, h)
    row_tile, group = pick_block_tile(nblk, h, b)
    grid = (b // row_tile, nblk // group)
    spec = pl.BlockSpec((row_tile, group, 2, h), lambda i, j: (i, j, 0, 0))
    out_re, out_im = pl.pallas_call(
        functools.partial(butterfly_stage_kernel, stage=stage),
        grid=grid,
        in_specs=[spec, spec],
        out_specs=[spec, spec],
        out_shape=[
            jax.ShapeDtypeStruct(re4.shape, jnp.float32),
            jax.ShapeDtypeStruct(im4.shape, jnp.float32),
        ],
        interpret=interpret,
        name="repro_butterfly_stage",
    )(re4.astype(jnp.float32), im4.astype(jnp.float32))
    return out_re.reshape(b, n), out_im.reshape(b, n)
