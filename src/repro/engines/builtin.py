"""The six seed engines, registered with their true capability envelopes.

The engine *bodies* keep living where they grew — the jnp schedules in
``repro.core`` and the fused Pallas kernels in ``repro.kernels`` — but
the planner no longer knows their names: everything it used to hardcode
(the ``PLAN_VARIANTS`` tuple, the fused-kind/device/VMEM gating in
``variant_candidates``, the per-variant cost tables in ``autotune``) now
reads off these specs.

Capability parity with the pre-registry planner is deliberate and tested:

* the four jnp engines serve every problem kind; the sharded
  ``fft2d_pencil`` kind only across two or more devices, and only while
  the pencil program's per-chip working set fits the chip's HBM;
* the fused kernels serve the 1D/2D complex+real kinds only, single
  device, power-of-two dims, and only while a 1D row tile fits the VMEM
  budget (``working_set``) — the exact gate ``variant_candidates`` used
  to open-code.
"""

from __future__ import annotations

import functools
import math

from repro.engines.registry import CostHints, EngineSpec, register_engine

#: Every planner kind: the jnp schedules are the universal fallback (the
#: stream/pencil/oaconv paths compose them per 1D pass).
_JNP_KINDS = (
    "fft1d", "fft2d", "fft2d_stream", "fft2d_pencil", "rfft1d", "rfft2d",
    "oaconv2d",
)

#: Kinds whose entry points dispatch to the fused Pallas kernels.
_FUSED_KINDS = ("fft1d", "fft2d", "rfft1d", "rfft2d")


def _core_ops(name: str):
    """Op factory shared by all builtin engines: the ``repro.core`` engine
    entries under a concrete variant (their dispatch chains terminate on
    builtin names, so this never re-enters the registry)."""

    def factory(kind: str, direction: str):
        inv = direction == "inv"
        if kind == "fft1d":
            from repro.core.fft1d import fft_impl, ifft_impl

            return functools.partial(ifft_impl if inv else fft_impl, variant=name)
        if kind == "fft2d":
            from repro.core.fft2d import fft2_impl, ifft2_impl

            return functools.partial(ifft2_impl if inv else fft2_impl, variant=name)
        if kind == "rfft1d":
            from repro.core.rfft import irfft_impl, rfft_impl

            return functools.partial(irfft_impl if inv else rfft_impl, variant=name)
        if kind == "rfft2d":
            from repro.core.rfft import irfft2_impl, rfft2_impl

            return functools.partial(irfft2_impl if inv else rfft2_impl, variant=name)
        if kind == "fft2d_stream" and not inv:
            from repro.core.fft2d import fft2_stream

            return functools.partial(fft2_stream, variant=name)
        if kind == "fft2d_pencil":
            from repro.core.distributed import pencil_fft2

            # Takes the sharded grid and the plan's ``chunks``; the mesh,
            # its axis and the layout are read from the grid's sharding.
            return functools.partial(pencil_fft2, variant=name, inverse=inv)
        # oaconv2d needs an (image, kernel) pair; it executes at the plan
        # level (repro.plan.execute), not here.
        return None

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
            ops=_core_ops(name),
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
            ops=_core_ops(name),
        ), _protect=True)


_register_builtin_engines()
