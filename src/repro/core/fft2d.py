"""Separable 2D FFT + the paper's ping-pong-buffered streaming processor.

The paper's 2D processor (fig. 3) runs two 1D FFT engines simultaneously:
engine 1 performs row FFTs of frame k into RAM1 while engine 2 reads frame
k−1's row-FFT result from RAM2 and produces the final column-FFT output; a
RAM controller flips ``sel`` when both RAMs fill.

``fft2_stream`` is the JAX dataflow rendition: a ``lax.scan`` whose carry is
"the other RAM" (the previous frame's row-FFT result). Within one scan step
the row-pass of frame k and the column-pass of frame k−1 have no data
dependency, so the XLA scheduler may execute them concurrently — the same
concurrency the two hardware engines provide. The ``sel`` wire disappears:
buffer rotation is the scan carry.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.fft1d import Variant, fft_impl, ifft_impl

__all__ = ["fft2_impl", "ifft2_impl", "fft2_stream", "fftshift2", "ifftshift2"]


def fft2_impl(x: jax.Array, *, variant: Variant) -> jax.Array:
    """2D FFT over the last two axes on jnp engine ``variant``: row pass
    then column pass (paper fig. 1), one pass program each."""
    y = fft_impl(x, axis=-1, variant=variant)     # first 1D FFT block (rows)
    return fft_impl(y, axis=-2, variant=variant)  # second 1D FFT block (columns)


def ifft2_impl(x: jax.Array, *, variant: Variant) -> jax.Array:
    """Inverse 2D FFT over the last two axes (1/(H·W)) on jnp engine
    ``variant``."""
    y = ifft_impl(x, axis=-1, variant=variant)
    return ifft_impl(y, axis=-2, variant=variant)


def fftshift2(x: jax.Array) -> jax.Array:
    """Centre the zero-frequency bin (for correlation/holography demos)."""
    return jnp.roll(x, shift=(x.shape[-2] // 2, x.shape[-1] // 2), axis=(-2, -1))


def ifftshift2(x: jax.Array) -> jax.Array:
    """Exact inverse of :func:`fftshift2`.

    Rolls by the *negated* half sizes: for even H/W that equals another
    ``fftshift2``, but for odd lengths (framing code pads to arbitrary
    sizes even though the engines are pow2-only) the half-size roll is not
    self-inverse and the sign matters.
    """
    return jnp.roll(
        x, shift=(-(x.shape[-2] // 2), -(x.shape[-1] // 2)), axis=(-2, -1)
    )


def fft2_stream(frames: jax.Array, *, variant: Variant, unroll: int = 1) -> jax.Array:
    """Streaming 2D FFT over ``frames[t, H, W]`` with ping-pong double buffering.

    Frame t's row pass and frame t−1's column pass execute in the same scan
    step (two concurrent engines). Output t is the 2D FFT of frame t — the
    one-frame pipeline latency is internal: a zero frame is fed through to
    drain the pipe, matching the hardware's drain cycle. ``unroll`` is the
    scan's unroll factor (a planned knob of the ``fft2d_stream`` kind).
    """
    if frames.ndim < 3:
        raise ValueError("fft2_stream expects (T, H, W) or (T, ..., H, W)")
    if not jnp.issubdtype(frames.dtype, jnp.complexfloating):
        frames = frames.astype(jnp.complex64)

    def step(ram, frame):
        # Engine 1: row FFTs of the incoming frame -> the "write" RAM.
        row_done = fft_impl(frame, axis=-1, variant=variant)
        # Engine 2 (concurrent): column FFTs of the previous frame's rows.
        out = fft_impl(ram, axis=-2, variant=variant)
        return row_done, out

    drain = jnp.zeros_like(frames[:1])
    stream = jnp.concatenate([frames, drain], axis=0)
    init_ram = jnp.zeros_like(frames[0])
    _, outs = jax.lax.scan(step, init_ram, stream, unroll=unroll)
    return outs[1:]  # drop the pipeline-fill output
