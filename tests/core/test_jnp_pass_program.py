"""Each 1D pass of a jnp engine is one compiled program, not one per op.

The jnp engines (``looped``, ``unrolled``, ``stockham``, ``radix4``) serve
every frame the fused kernels cannot hold. Dispatched op by op, a pass
over a large stack costs one device program (and, on the TPU, one split
and combine of every complex64 array) per jnp op. These tests pin the
pass to one top-level jit equation with a stable name, and check that the
compiled passes still match the float64 numpy reference through the
``repro.xfft`` front door, called eagerly and inside an outer ``jax.jit``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.xfft as xfft
from repro.core.fft1d import fft_impl, ifft_impl
from repro.core.rfft import irfft_impl, rfft2_impl, rfft_impl

JNP_VARIANTS = ["looped", "unrolled", "stockham", "radix4"]

# (pass, entry, input shape, input dtype, program name)
PASSES = {
    "fft_rows": (lambda x, v: fft_impl(x, axis=-1, variant=v),
                 (2, 8, 16), jnp.complex64, "repro_jnp_fft_pass"),
    "fft_columns": (lambda x, v: fft_impl(x, axis=-2, variant=v),
                    (2, 8, 16), jnp.complex64, "repro_jnp_fft_pass"),
    "fft_real_input": (lambda x, v: fft_impl(x, axis=-1, variant=v),
                       (2, 8, 16), jnp.float32, "repro_jnp_fft_pass"),
    "ifft": (lambda x, v: ifft_impl(x, axis=-1, variant=v),
             (2, 8, 16), jnp.complex64, "repro_jnp_fft_pass"),
    "rfft": (lambda x, v: rfft_impl(x, axis=-1, variant=v),
             (2, 8, 16), jnp.float32, "repro_jnp_rfft_pass"),
    "irfft": (lambda x, v: irfft_impl(x, axis=-1, variant=v),
              (2, 8, 9), jnp.complex64, "repro_jnp_irfft_pass"),
}


def _top_level_programs(fn, x):
    jaxpr = jax.make_jaxpr(fn)(x).jaxpr
    return [(eqn.primitive.name, eqn.params.get("name")) for eqn in jaxpr.eqns]


@pytest.mark.parametrize("pass_name", sorted(PASSES))
@pytest.mark.parametrize("variant", JNP_VARIANTS)
def test_jnp_pass_is_one_program(variant, pass_name):
    entry, shape, dtype, program = PASSES[pass_name]
    x = jnp.zeros(shape, dtype)
    eqns = _top_level_programs(lambda a: entry(a, variant), x)
    assert len(eqns) == 1, eqns
    primitive, name = eqns[0]
    assert primitive in ("pjit", "jit")
    assert name == program


@pytest.mark.parametrize("variant", JNP_VARIANTS)
def test_rfft2_jnp_dispatches_one_program_per_pass(variant):
    x = jnp.zeros((3, 16, 8), jnp.float32)
    eqns = _top_level_programs(lambda a: rfft2_impl(a, variant=variant), x)
    assert [name for _, name in eqns] == [
        "repro_jnp_rfft_pass",   # rows, under fft.rows
        "repro_jnp_fft_pass",    # columns, under fft.columns
    ]


# Odd and even log2 sizes, non-square frames both ways, leading batch dims.
SHAPES = [(8, 8), (16, 16), (8, 32), (32, 8), (2, 3, 16, 8)]


def _reference(op, x):
    x64 = x.astype(np.float64)
    if op == "rfft2":
        return x, np.fft.rfft2(x64)
    if op == "fft2":
        return x, np.fft.fft2(x64)
    spec = np.fft.rfft2(x64)
    return spec.astype(np.complex64), np.fft.irfft2(spec)


@pytest.mark.parametrize("outer_jit", [False, True], ids=["eager", "outer_jit"])
@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: "x".join(map(str, s)))
@pytest.mark.parametrize("op", ["rfft2", "irfft2", "fft2"])
@pytest.mark.parametrize("variant", ["radix4", "stockham"])
def test_compiled_passes_match_numpy(variant, op, shape, outer_jit):
    x = np.random.default_rng(sum(shape)).standard_normal(shape).astype(np.float32)
    arg, ref = _reference(op, x)
    fn = getattr(xfft, op)
    if outer_jit:
        fn = jax.jit(fn)
    with xfft.config(variant=variant):
        got = np.asarray(fn(jnp.asarray(arg)))
    assert got.shape == ref.shape
    scale = max(1.0, float(np.max(np.abs(ref))))
    np.testing.assert_allclose(got / scale, ref / scale, atol=1e-5)
