"""Pencil-decomposed multi-device 2D FFT under ``shard_map``.

The paper's two 1D engines + ping-pong RAM become, on a TPU mesh:

  local row FFTs  →  all_to_all "corner-turn" transpose  →  local column FFTs

The all_to_all is the distributed analogue of the RAM1/RAM2 handoff: it is
the only inter-engine communication, and the chunked variant overlaps it with
butterfly compute the same way the hardware overlaps engine 1's writes with
engine 2's reads.

Layouts (for a 1D device axis of size d):
  input   x:  rows sharded    — global (H, W), per-device (H/d, W)
  output  y:  columns sharded — global (H, W), per-device (H, W/d)
"""

from __future__ import annotations

import functools
from typing import Literal, Union

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.fft1d import Variant, fft_impl

__all__ = ["fft2_pencil", "fft2_pencil_overlapped", "pencil_sharding"]


def pencil_sharding(mesh: Mesh, axis: str, stage: Literal["rows", "cols"]):
    """NamedSharding for the pencil layouts (batch dims replicated)."""
    if stage == "rows":
        return NamedSharding(mesh, P(axis, None))
    return NamedSharding(mesh, P(None, axis))


def _corner_turn(block: jax.Array, axis_name: str, d: int) -> jax.Array:
    """all_to_all transpose: (H/d, W) row-pencils -> (H, W/d) column-pencils."""
    h_loc, w = block.shape[-2], block.shape[-1]
    lead = block.shape[:-2]
    # Split the row-FFT result into d column chunks and exchange them.
    blk = block.reshape(*lead, h_loc, d, w // d)
    blk = jnp.moveaxis(blk, -2, 0)  # (d, ..., H/d, W/d)
    blk = jax.lax.all_to_all(blk, axis_name, split_axis=0, concat_axis=0, tiled=False)
    # (d, ..., H/d, W/d): leading dim now indexes the source device = row block.
    blk = jnp.moveaxis(blk, 0, -3)  # (..., d, H/d, W/d)
    return blk.reshape(*lead, h_loc * d, w // d)


def fft2_pencil(
    x: jax.Array,
    mesh: Mesh,
    axis: str = "data",
    variant: Variant = "looped",
) -> jax.Array:
    """Distributed 2D FFT. ``x`` global (..., H, W) sharded (axis, None)."""
    d = mesh.shape[axis]
    if variant == "auto":
        from repro.plan.api import resolve  # lazy: plan imports core

        variant = resolve("fft2d_pencil", tuple(x.shape), n_devices=d).variant
    ndim = jnp.ndim(x)
    lead = (None,) * (ndim - 2)

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=P(*lead, axis, None),
        out_specs=P(*lead, None, axis),
    )
    def _run(block):
        rows = fft_impl(block, axis=-1, variant=variant)       # engine 1 (local)
        turned = _corner_turn(rows, axis, d)              # RAM handoff
        return fft_impl(turned, axis=-2, variant=variant)      # engine 2 (local)

    return _run(x.astype(jnp.complex64))


def fft2_pencil_overlapped(
    x: jax.Array,
    mesh: Mesh,
    axis: str = "data",
    variant: Variant = "looped",
    chunks: Union[int, Literal["auto"]] = "auto",
) -> jax.Array:
    """Chunked pencil FFT overlapping the corner-turn with column compute.

    The W axis is split into ``chunks`` slabs; slab i's all_to_all has no
    data dependency on slab i−1's column FFT, so the scheduler can overlap
    collective i with compute i−1 — the ping-pong insight applied to the
    collective itself (beyond-paper optimization, see EXPERIMENTS.md §Perf).

    ``chunks="auto"`` (default) and ``variant="auto"`` take their values
    from the ``repro.plan`` plan for this ``(shape, n_devices)`` problem.
    """
    d = mesh.shape[axis]
    if variant == "auto" or chunks == "auto":
        from repro.plan.api import resolve  # lazy: plan imports core

        plan = resolve("fft2d_pencil", tuple(x.shape), n_devices=d)
        if variant == "auto":
            variant = plan.variant
        if chunks == "auto":
            chunks = plan.chunks
    ndim = jnp.ndim(x)
    lead = (None,) * (ndim - 2)
    w = x.shape[-1]
    sub = w // (d * chunks)

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=P(*lead, axis, None),
        out_specs=P(*lead, None, axis),
    )
    def _run(block):
        rows = fft_impl(block, axis=-1, variant=variant)   # (..., H/d, W)
        h_loc = rows.shape[-2]
        # Slab c holds the c-th ``sub``-wide piece of EVERY device's final
        # column block, so each device's slabs concatenate into its own
        # contiguous W/d columns: the result stays column-sharded instead
        # of being gathered whole onto every device.
        pieces = rows.reshape(*rows.shape[:-1], d, chunks, sub)
        outs = []
        for c in range(chunks):
            slab = pieces[..., c, :].reshape(*rows.shape[:-2], h_loc, d * sub)
            turned = _corner_turn(slab, axis, d)          # (..., H, sub)
            outs.append(fft_impl(turned, axis=-2, variant=variant))
        return jnp.concatenate(outs, axis=-1)             # (..., H, W/d)

    return _run(x.astype(jnp.complex64))
