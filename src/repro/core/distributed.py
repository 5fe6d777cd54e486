"""Pencil-decomposed multi-device 2D FFT under ``shard_map``.

The paper's two 1D engines + ping-pong RAM become, on a TPU mesh:

  local FFTs along the whole axis  →  all_to_all "corner-turn"  →  local FFTs
  along the other axis

The all_to_all is the distributed analogue of the RAM1/RAM2 handoff: it is
the only inter-engine communication, and the chunked variant overlaps it with
butterfly compute the same way the hardware overlaps engine 1's writes with
engine 2's reads.

Layouts (for a 1D device axis of size d; batch axes replicated):
  "rows": global (H, W), per-device (H/d, W) — rows first, output "cols"
  "cols": global (H, W), per-device (H, W/d) — columns first, output "rows"

So ``ifft2(fft2(x))`` returns ``x``'s own layout, and nothing is gathered.
The program is jitted under a stable name (``repro_pencil_fft2``,
``repro_pencil_ifft2``) that the device trace shows; its local passes are
the jnp engines' pass programs (``repro.core.fft1d.repro_jnp_fft_pass``).
"""

from __future__ import annotations

import functools
from typing import Literal, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import obs
from repro.core.fft1d import repro_jnp_fft_pass

__all__ = [
    "pencil_fft2",
    "pencil_layout",
    "pencil_sharding",
]

#: The layout the pencil program leaves for each input layout.
_OUT_LAYOUT = {"rows": "cols", "cols": "rows"}


def pencil_sharding(mesh: Mesh, axis: str, stage: Literal["rows", "cols"], ndim: int = 2):
    """NamedSharding for the pencil layouts (batch dims replicated)."""
    lead = (None,) * (ndim - 2)
    if stage == "rows":
        return NamedSharding(mesh, P(*lead, axis, None))
    return NamedSharding(mesh, P(*lead, None, axis))


def pencil_layout(x) -> Optional[Tuple[Mesh, str, str]]:
    """``(mesh, mesh axis, layout)`` when ``x`` is a jax array sharded over
    more than one device along exactly one of its last two axes, by one mesh
    axis whose size divides both, every other axis replicated; else None."""
    try:
        sharding = x.sharding
    except Exception:  # noqa: BLE001 — tracers and host arrays carry none
        return None
    if not isinstance(sharding, NamedSharding) or x.ndim < 2:
        return None
    spec = tuple(sharding.spec) + (None,) * (x.ndim - len(sharding.spec))
    sharded = [(i, s) for i, s in enumerate(spec) if s is not None]
    if len(sharded) != 1:
        return None
    dim, name = sharded[0]
    if isinstance(name, tuple):
        if len(name) != 1:
            return None
        name = name[0]
    d = sharding.mesh.shape[name]
    h, w = x.shape[-2], x.shape[-1]
    if d < 2 or dim < x.ndim - 2 or h % d or w % d:
        return None
    return sharding.mesh, name, "rows" if dim == x.ndim - 2 else "cols"


def _slabs(a: jax.Array, axis: int, d: int, chunks: int):
    """Split ``axis`` (length d·chunks·sub) into ``chunks`` slabs of length
    d·sub. Slab c holds the c-th ``sub``-long piece of EVERY device's final
    block, so each device's slabs concatenate into its own contiguous block:
    the result stays sharded instead of being gathered."""
    shape = a.shape
    sub = shape[axis] // (d * chunks)
    pieces = a.reshape(shape[:axis] + (d, chunks, sub) + shape[axis + 1:])
    for c in range(chunks):
        slab = jax.lax.index_in_dim(pieces, c, axis=axis + 1, keepdims=False)
        yield slab.reshape(shape[:axis] + (d * sub,) + shape[axis + 1:])


def _pencil_program(x, mesh, axis, layout, variant, chunks, inverse):
    """The pencil program body: local pass, corner turn and local pass,
    ``chunks`` slabs at a time; the inverse by conjugation with the 1/(H·W)
    scale applied once."""
    d = mesh.shape[axis]
    ndim = x.ndim
    h, w = x.shape[-2], x.shape[-1]
    # The axis each device holds whole (transformed first) and the sharded
    # one (whole only after the corner turn).
    whole, split = (ndim - 1, ndim - 2) if layout == "rows" else (ndim - 2, ndim - 1)
    in_sharding = pencil_sharding(mesh, axis, layout, ndim)
    out_sharding = pencil_sharding(mesh, axis, _OUT_LAYOUT[layout], ndim)

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=in_sharding.spec,
                       out_specs=out_sharding.spec)
    def run(block):
        if inverse:
            block = jnp.conj(block)
        first = repro_jnp_fft_pass(block, axis=whole, variant=variant, inverse=False)
        outs = []
        for slab in _slabs(first, whole, d, chunks):
            turned = jax.lax.all_to_all(slab, axis, split_axis=whole,
                                        concat_axis=split, tiled=True)
            outs.append(repro_jnp_fft_pass(turned, axis=split, variant=variant,
                                           inverse=False))
        y = outs[0] if chunks == 1 else jnp.concatenate(outs, axis=whole)
        if inverse:
            y = jnp.conj(y) * jnp.float32(1.0 / (h * w))
        return y

    return run(x.astype(jnp.complex64))


_STATIC = ("mesh", "axis", "layout", "variant", "chunks")


@functools.partial(jax.jit, static_argnames=_STATIC)
def repro_pencil_fft2(x, mesh, axis, layout, variant, chunks):
    """Forward pencil 2D FFT over the last two axes, as one program.

    The corner turn goes in ``chunks`` slabs: slab i's all_to_all has no
    data dependency on slab i-1's column FFT, so the scheduler can overlap
    collective i with compute i-1 (the ping-pong insight applied to the
    collective itself). The planner picks ``chunks`` for a sharded
    front-door call; call this directly only to pin it."""
    return _pencil_program(x, mesh, axis, layout, variant, chunks, inverse=False)


@functools.partial(jax.jit, static_argnames=_STATIC)
def repro_pencil_ifft2(x, mesh, axis, layout, variant, chunks):
    """Inverse pencil 2D FFT over the last two axes (1/(H·W)), as one program."""
    return _pencil_program(x, mesh, axis, layout, variant, chunks, inverse=True)


def pencil_fft2(x: jax.Array, *, variant: str, inverse: bool = False,
                chunks: int = 1) -> jax.Array:
    """The pencil engines' executor: a 2D FFT of ``x``, which is sharded as
    :func:`pencil_layout` accepts, read from its own sharding (mesh, axis,
    layout). Returns the other layout; nothing is gathered."""
    found = pencil_layout(x)
    if found is None:
        raise ValueError(
            "a pencil plan needs an array sharded over one mesh axis of two or "
            "more devices along one of its last two axes; got sharding "
            f"{getattr(x, 'sharding', None)!r} for shape {getattr(x, 'shape', None)}"
        )
    mesh, axis, layout = found
    program = repro_pencil_ifft2 if inverse else repro_pencil_fft2
    with obs.span("pencil.dispatch", n_devices=mesh.shape[axis], axis=axis,
                  layout_in=layout, layout_out=_OUT_LAYOUT[layout], chunks=chunks,
                  variant=variant, inverse=inverse):
        return program(x, mesh=mesh, axis=axis, layout=layout, variant=variant,
                       chunks=chunks)
