"""The six seed engines, registered with their true capability envelopes.

This module is where a builtin engine's name becomes code. The bodies
live below it: the jnp schedules in ``repro.core`` (pass programs, the 2D
compositions, the ping-pong stream, the pencil program) and the fused
Pallas kernels in ``repro.kernels``. Each engine's op factory binds its
variant or radix to those bodies; everything the planner used to
hardcode (the fused-kind/device/VMEM gating, the per-variant cost tables)
reads off these specs.

Capability parity with the pre-registry planner is deliberate and tested:

* the four jnp engines serve every problem kind; the sharded
  ``fft2d_pencil`` kind only across two or more devices, and only while
  the pencil program's per-chip working set fits the chip's HBM;
* the fused kernels serve the 1D/2D complex+real kinds only, single
  device, power-of-two dims, and only while a 1D row tile fits the VMEM
  budget (``working_set``).
"""

from __future__ import annotations

import functools
import math

import jax.numpy as jnp

from repro.core import fft1d, fft2d, rfft
from repro.core.distributed import pencil_fft2
from repro.engines.registry import CostHints, EngineSpec, register_engine
from repro.resilience import faults

#: Every planner kind: the jnp schedules are the universal fallback (the
#: stream/pencil/oaconv paths compose them per 1D pass).
_JNP_KINDS = (
    "fft1d", "fft2d", "fft2d_stream", "fft2d_pencil", "rfft1d", "rfft2d",
    "oaconv2d",
)

#: Kinds whose entry points dispatch to the fused Pallas kernels.
_FUSED_KINDS = ("fft1d", "fft2d", "rfft1d", "rfft2d")

#: (forward, inverse) core body per kind for the jnp engines; the pencil
#: kind takes the plan's ``chunks`` and oaconv2d executes at the plan level
#: (``repro.plan.execute``), not here.
_JNP_BODIES = {
    "fft1d": (fft1d.fft_impl, fft1d.ifft_impl),
    "fft2d": (fft2d.fft2_impl, fft2d.ifft2_impl),
    "rfft1d": (rfft.rfft_impl, rfft.irfft_impl),
    "rfft2d": (rfft.rfft2_impl, rfft.irfft2_impl),
    "fft2d_stream": (fft2d.fft2_stream, None),
    "fft2d_pencil": (functools.partial(pencil_fft2, inverse=False),
                     functools.partial(pencil_fft2, inverse=True)),
}


def _jnp_ops(name: str):
    """Op factory of a jnp engine: the core bodies under variant ``name``."""

    def factory(kind: str, direction: str):
        body = _JNP_BODIES.get(kind, (None, None))[direction == "inv"]
        return None if body is None else functools.partial(body, variant=name)

    return factory


def _fused_fft1d(x, axis: int = -1, *, radix: int, inverse: bool):
    """fft1d on the fused kernel: complex64 with the transform axis last;
    the inverse by the conjugation identity."""
    from repro.kernels.ops import fft_kernel  # lazy: pallas import

    x = jnp.moveaxis(x.astype(jnp.complex64), axis, -1)
    if inverse:
        y = jnp.conj(fft_kernel(jnp.conj(x), radix=radix)) / x.shape[-1]
    else:
        y = fft_kernel(x, radix=radix)
    return jnp.moveaxis(y, -1, axis)


def _fused_rfft1d(x, axis: int = -1, *, radix: int, inverse: bool):
    """rfft1d on the fused kernels, with the transform axis moved last."""
    from repro.kernels.ops import irfft_kernel, rfft_kernel  # lazy: pallas

    if inverse:
        x, kernel = x.astype(jnp.complex64), irfft_kernel
    else:
        x, kernel = rfft._ensure_real(x, "rfft").astype(jnp.float32), rfft_kernel
    return jnp.moveaxis(kernel(jnp.moveaxis(x, axis, -1), radix=radix), -1, axis)


def _fused_2d(x, *, kind: str, radix: int):
    """One fused 2D kernel call; a ``vmem`` fault injected at the
    ``kernel.fused`` seam sends a frame that fits to the unfused failover."""
    from repro.kernels import ops  # lazy: pallas import

    entry, unfused, real = {
        "fft2d": (ops.fft2_kernel, ops.fft2_unfused, False),
        "rfft2d": (ops.rfft2_kernel, ops.rfft2_unfused, True),
        "irfft2d": (ops.irfft2_kernel, ops.irfft2_unfused, True),
    }[kind]
    h = x.shape[-2]
    w = 2 * (x.shape[-1] - 1) if kind == "irfft2d" else x.shape[-1]
    if ops.fft2_fits_budget(h, w, real=real) and faults.vmem_exhausted(
        "kernel.fused", kind=kind, h=h, w=w
    ):
        return unfused(x, radix=radix)
    return entry(x, radix=radix)


def _fused_fft2d(x, *, radix: int, inverse: bool):
    """fft2d on the fused kernel; the inverse by the conjugation identity."""
    if not inverse:
        return _fused_2d(x, kind="fft2d", radix=radix)
    h, w = x.shape[-2], x.shape[-1]
    return jnp.conj(_fused_2d(jnp.conj(x), kind="fft2d", radix=radix)) / (h * w)


def _fused_rfft2d(x, *, radix: int, inverse: bool):
    """rfft2d on the fused kernels: float32 in (forward), complex64 in
    (inverse)."""
    if inverse:
        return _fused_2d(x.astype(jnp.complex64), kind="irfft2d", radix=radix)
    x = rfft._ensure_real(x, "rfft2").astype(jnp.float32)
    return _fused_2d(x, kind="rfft2d", radix=radix)


_FUSED_BODIES = {
    "fft1d": _fused_fft1d,
    "fft2d": _fused_fft2d,
    "rfft1d": _fused_rfft1d,
    "rfft2d": _fused_rfft2d,
}


def _fused_ops(radix: int):
    """Op factory of a fused engine: the Pallas kernels at ``radix``."""

    def factory(kind: str, direction: str):
        body = _FUSED_BODIES.get(kind)
        if body is None:
            return None
        return functools.partial(body, radix=radix, inverse=direction == "inv")

    return factory


#: Per-chip working set of the pencil program, in blocks of the grid's
#: per-chip share (8·H·W/d bytes): the input, the output and at most 2.56
#: blocks of temporaries (each of the four jnp variants compiled for a v5e
#: 2x2 at 32768², chunks 1-16; 1.5-2.0 at chunks 1).
_PENCIL_BLOCKS = 4.6


def _jnp_predicate(key) -> bool:
    """The jnp engines serve a pencil key only across two or more devices,
    and on a TPU only where the pencil program's per-chip working set fits
    the chip's HBM (no chunking shrinks it: the first pass holds whole
    blocks)."""
    if key.kind != "fft2d_pencil":
        return True
    if key.n_devices < 2:
        return False
    if key.backend != "tpu":
        return True
    from repro.launch.roofline import chip_peaks  # lazy: launch imports jax

    elem = 16 if key.precision == "double" else 8
    block = elem * math.prod(key.shape) / key.n_devices
    return _PENCIL_BLOCKS * block <= chip_peaks(key.device_kind).hbm_bytes


def _fused_predicate(key) -> bool:
    """Fused kernels need power-of-two transform dims (and a real 2D frame
    to actually be 2D), and a backend that runs them: compiled on the TPU,
    interpreted on the CPU."""
    if key.backend not in ("tpu", "cpu"):
        return False
    if key.kind in ("fft2d", "rfft2d"):
        if len(key.shape) < 2:
            return False
        dims = key.shape[-2:]
    else:
        dims = key.shape[-1:]
    return all(d >= 2 and (d & (d - 1)) == 0 for d in dims)


def _fused_working_set(key):
    """Smallest VMEM residency the fused path needs: the smallest legal 1D
    row tile of the longest transform dim (the 2D kernels' unfused failover
    still runs the 1D kernel per pass, so a row tile must fit for ANY fused
    plan)."""
    if key.kind in ("fft2d", "rfft2d"):
        if len(key.shape) < 2:
            return None
        dims = key.shape[-2:]
    else:
        dims = key.shape[-1:]
    from repro.kernels.fft_radix2 import fft1_working_set  # lazy: pallas

    return fft1_working_set(max(dims))


def _register_builtin_engines() -> None:
    # The four jnp schedules: per-variant memory-traffic factors and
    # dispatch overheads exactly as the pre-registry cost tables had them.
    jnp_engines = (
        ("looped", CostHints(traffic_factor=6.0, stage_overhead_s=3.0e-6,
                             entry_overhead_s=5.0e-6), 2),
        ("unrolled", CostHints(traffic_factor=6.0, stage_overhead_s=0.5e-6), 2),
        ("stockham", CostHints(traffic_factor=4.0, stage_overhead_s=0.8e-6), 2),
        ("radix4", CostHints(traffic_factor=4.0, stage_overhead_s=0.8e-6,
                             flop_scale=0.85), 4),
    )
    for name, cost, radix in jnp_engines:
        register_engine(EngineSpec(
            name=name,
            backend="jnp",
            kinds=_JNP_KINDS,
            radix=radix,
            cost=cost,
            ops=_jnp_ops(name),
            predicate=_jnp_predicate,
            # stockham is the canonical always-works rung: pure jnp ops,
            # every kind, no VMEM cliff — the degradation ladder's bottom.
            reliable=(name == "stockham"),
        ), _protect=True)
    for name, radix, flop_scale in (("fused", 2, 1.0), ("fused_r4", 4, 0.85)):
        register_engine(EngineSpec(
            name=name,
            backend="pallas",
            kinds=_FUSED_KINDS,
            radix=radix,
            fused=True,
            single_device_only=True,
            working_set=_fused_working_set,
            predicate=_fused_predicate,
            cost=CostHints(traffic_factor=4.0, stage_overhead_s=0.8e-6,
                           flop_scale=flop_scale),
            ops=_fused_ops(radix),
        ), _protect=True)


_register_builtin_engines()
