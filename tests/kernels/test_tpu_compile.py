"""Compile the fused FFT kernels for a described TPU v5e chip.

No chip is needed: the TPU compiler compiles for a topology that is
described, not attached. Each entry point is compiled at the size the chip
smoke run serves, and must lower to a Mosaic kernel (``tpu_custom_call``)
that the compiler accepts inside the kernels' scoped-VMEM limit, with no
HBM temporaries where the transform is one round trip.
"""

import math
import pathlib
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import fft_radix2 as k
from repro.kernels.butterfly import butterfly_stage
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.slstm_scan import slstm_scan


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    """Programs compiled for a described chip are written to the persistent
    cache but cannot be read back without one; keep them out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _f32(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def _steps_1d(shape, n):
    return shape[0] // k.pick_row_tile(shape[0], n)


# name -> (entry point, input shapes, grid steps, HBM temporaries allowed)
CASES = {
    "fft_fused_r2": (lambda a, b: k.fft_fused(a, b, radix=2), [(256, 1024)] * 2,
                     _steps_1d((256, 1024), 1024), False),
    "fft_fused_r4": (lambda a, b: k.fft_fused(a, b, radix=4), [(256, 1024)] * 2,
                     _steps_1d((256, 1024), 1024), False),
    "fft2_fused_r4_512": (lambda a, b: k.fft2_fused(a, b, radix=4), [(8, 512, 512)] * 2,
                          8, False),
    "rfft_fused_r4": (lambda a: k.rfft_fused(a, radix=4), [(256, 1024)],
                      _steps_1d((256, 1024), 1024), True),
    "irfft_fused_r4": (lambda a, b: k.irfft_fused(a, b, radix=4), [(256, 513)] * 2,
                       _steps_1d((256, 1024), 1024), True),
    # The census's most elongated complex frame at radix 2: the largest
    # scoped-VMEM need of any block inside the budget.
    "fft2_fused_r2_128x2048": (lambda a, b: k.fft2_fused(a, b, radix=2),
                               [(4, 128, 2048)] * 2, 4, False),
    "rfft2_fused_r4_512": (lambda a: k.rfft2_fused(a, radix=4), [(8, 512, 512)], 8, False),
    "irfft2_fused_r4_512": (lambda a, b: k.irfft2_fused(a, b, radix=4),
                            [(8, 512, 257)] * 2, 8, False),
    # Lane (b) of the smoke run: 2048x2048 real frames are over the 2D
    # census, so their rows go through the 1D real kernel.
    "rfft_fused_r4_rows_2048": (lambda a: k.rfft_fused(a, radix=4), [(32768, 2048)],
                                _steps_1d((32768, 2048), 2048), True),
    # Rows too long to turn 128 of them: the four-step (N/128, 128) layout,
    # from the shortest such row to the longest the census admits.
    "fft_fused_r2_rows_4096": (lambda a, b: k.fft_fused(a, b, radix=2), [(64, 4096)] * 2,
                               _steps_1d((64, 4096), 4096), False),
    "fft_fused_r4_rows_262144": (lambda a, b: k.fft_fused(a, b, radix=4),
                                 [(2, 1 << 18)] * 2, _steps_1d((2, 1 << 18), 1 << 18), False),
    "rfft_fused_r4_rows_16384": (lambda a: k.rfft_fused(a, radix=4), [(8, 16384)],
                                 _steps_1d((8, 16384), 16384), True),
    "irfft_fused_r4_rows_16384": (lambda a, b: k.irfft_fused(a, b, radix=4),
                                  [(8, 8193)] * 2, _steps_1d((8, 16384), 16384), True),
}


@pytest.mark.parametrize("name", list(CASES))
def test_fused_entry_point_compiles_for_v5e(one_chip, name):
    fn, shapes, steps, temps_ok = CASES[name]
    args = [_f32(s, one_chip) for s in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    # (the compiler may pad an odd half-spectrum width in its layout)
    assert mem.argument_size_in_bytes >= sum(4 * math.prod(s) for s in shapes)
    if not temps_ok:
        # One HBM round trip: nothing is staged in HBM between passes.
        assert mem.temp_size_in_bytes == 0
    # The blocks one grid step moves in and out fit the census budget the
    # planner tiles against; the compiler placed them, double-buffered,
    # with the kernel's working values inside the scoped-VMEM limit.
    per_step = (mem.argument_size_in_bytes + mem.output_size_in_bytes) / steps
    assert per_step <= k._VMEM_BUDGET_BYTES < k._VMEM_LIMIT_BYTES


def test_census_frame_fills_the_budget():
    """512x512 complex is the largest square frame the fused 2D kernel
    takes (the smoke run's lane a); 2048x2048 real is over it (lane b)."""
    assert k.fft2_fits_vmem(512, 512)
    assert not k.fft2_fits_vmem(1024, 1024)
    assert not k.fft2_fits_vmem(2048, 2048, arrays=6)
    assert k.fft_fits_vmem(2048)
    assert k.pick_row_tile(32768, 2048) % 8 == 0
    # Longer rows fit one per block in the four-step layout, up to 2^18.
    assert k.fft_fits_vmem(1 << 18) and not k.fft_fits_vmem(1 << 19)


# kernel name -> (entry point, input shapes): one entry per ``pallas_call``
# under ``src/repro/kernels/``.
NAMED = {
    "repro_fft_fused": (lambda a, b: k.fft_fused(a, b, radix=4), [(256, 1024)] * 2),
    "repro_fft_fused_split": (lambda a, b: k.fft_fused(a, b, radix=2), [(64, 4096)] * 2),
    "repro_rfft_fused": (lambda a: k.rfft_fused(a, radix=4), [(256, 1024)]),
    "repro_irfft_fused": (lambda a, b: k.irfft_fused(a, b, radix=4), [(256, 513)] * 2),
    "repro_fft2_fused": (lambda a, b: k.fft2_fused(a, b, radix=4), [(2, 512, 512)] * 2),
    "repro_rfft2_fused": (lambda a: k.rfft2_fused(a, radix=4), [(2, 512, 512)]),
    "repro_irfft2_fused": (lambda a, b: k.irfft2_fused(a, b, radix=4), [(2, 512, 257)] * 2),
    "repro_butterfly_stage": (lambda a, b: butterfly_stage(a, b, stage=2), [(8, 256)] * 2),
    "repro_flash_attention_fwd": (flash_attention_fwd, [(2, 256, 128)] * 3),
    "repro_slstm_scan": (lambda *args: slstm_scan(*args, chunk=8),
                         [(2, 32, 256), (4, 16, 64), (256,)] + [(2, 64)] * 4),
}


def test_every_pallas_call_is_named():
    """Each ``pallas_call`` in the kernels package passes a ``repro_`` name,
    and each name is lowered below."""
    src = pathlib.Path(k.__file__).parent
    calls = names = 0
    for path in src.glob("*.py"):
        text = path.read_text()
        calls += text.count("pl.pallas_call(")
        found = re.findall(r'name="(repro_\w+)"', text)
        names += len(found)
        assert set(found) <= set(NAMED), path
    assert calls == names == len(NAMED)


@pytest.mark.parametrize("name", list(NAMED))
def test_pallas_call_lowers_with_its_name(one_chip, name):
    """The name reaches the Mosaic call, whose HLO instruction (and so the
    operation in a device trace) is named after it."""
    fn, shapes = NAMED[name]
    lowered = jax.jit(fn).lower(*[_f32(s, one_chip) for s in shapes])
    assert re.findall(r'kernel_name = "([^"]+)"', lowered.as_text()) == [name]


def test_compiled_kernel_keeps_its_name(one_chip):
    """Compiled for the chip, the kernel's instruction carries the name the
    trace reduction reports (``jit_rfft2_fused/repro_rfft2_fused``)."""
    fn, shapes = NAMED["repro_rfft2_fused"]
    text = jax.jit(fn).lower(*[_f32(s, one_chip) for s in shapes]).compile().as_text()
    assert re.search(r"%repro_rfft2_fused(\.\d+)? = .*custom_call_target=\"tpu_custom_call\"",
                     text)


def test_rfft2_program_assembles_its_result_in_place(one_chip, record_property):
    """The fused rfft2 entry point compiles to one program: the kernel,
    still named ``repro_rfft2_fused`` (the only ``repro_`` instruction, so
    the pallas share reads only the kernel), one ``X64Combine`` (the
    complex64 write), which is the program's result (no copy of the
    complex64 array after it), and one complex64 half spectrum out."""
    from repro.kernels.ops import repro_rfft2_kernel

    f, h, w = 8, 512, 512
    compiled = repro_rfft2_kernel.lower(_f32((f, h, w), one_chip), radix=4,
                                        interpret=False).compile()
    text = compiled.as_text()
    entry = text[text.index("\nENTRY"):]
    named = re.findall(r"%(repro_\w+?)(?:\.\d+)? = ", entry)
    assert named == ["repro_rfft2_fused"]
    assert entry.count('custom_call_target="X64Combine"') == 1
    assert re.search(r'ROOT %\S+ = c64\S+ custom-call\(.*custom_call_target="X64Combine"', entry)
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes == f * h * (w // 2 + 1) * 8
    record_property("temp_size_in_bytes", mem.temp_size_in_bytes)


@pytest.mark.parametrize("variant", ["looped", "unrolled", "stockham", "radix4"])
@pytest.mark.parametrize("layout,inverse,chunks",
                         [("rows", True, 1), ("cols", False, 4), ("rows", False, 16)])
def test_pencil_program_fits_the_planners_hbm_model(topo, layout, inverse, chunks, variant):
    """The pencil program at the grid cell's size (32768² complex64 over a
    v5e 2x2) compiles with one all-to-all per slab, and its per-chip bytes
    (argument, output and temporaries) stay inside the working set the
    planner's HBM gate assumes for every jnp engine's pencil rung, from one
    slab to the planner's largest slab count (16)."""
    import numpy as np
    from jax.sharding import Mesh

    from repro.core import distributed
    from repro.engines import builtin

    mesh = Mesh(np.array(topo.devices), ("data",))
    n = 32768
    x = jax.ShapeDtypeStruct((n, n), jnp.complex64,
                             sharding=distributed.pencil_sharding(mesh, "data", layout))
    program = distributed.repro_pencil_ifft2 if inverse else distributed.repro_pencil_fft2
    compiled = program.lower(x, mesh=mesh, axis="data", layout=layout, variant=variant,
                             chunks=chunks).compile()
    text = compiled.as_text()
    assert len(re.findall(r"= \S+ all-to-all\(", text)) == 2 * chunks  # re and im parts
    mem = compiled.memory_analysis()
    block = 8 * n * n // 4
    assert mem.argument_size_in_bytes == mem.output_size_in_bytes == block
    per_chip = mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes
    assert per_chip <= builtin._PENCIL_BLOCKS * block
