"""Pallas sLSTM scan kernel — VMEM-resident recurrent state.

The dry-run shows xlstm-350m × prefill_32k as the worst cell in the
roofline table (memory term 260 s): the XLA while-loop writes the (c, n,
h, m) carry and reads per-step slices from HBM on every one of 32768
timesteps. This kernel runs the recurrence in chunks with the state held
in VMEM scratch across the whole sequence — HBM traffic collapses to one
read of the gate pre-activations and one write of the hidden outputs
(≈10× less), the butterfly-reuse insight applied to a recurrence.

ABI: xg (B, L, 4D) f32 gate pre-activations (x @ Wx, computed outside —
that part is a dense matmul XLA already does well), wr (H, hd, 4hd)
block-diagonal recurrent weights, bias (4D,), initial state (B, D) × 4.
Returns hs (B, L, D) and the final state.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_SLSTM_HEADS = 4


def _kernel(xg_ref, wr_ref, bias_ref, c0_ref, n0_ref, h0_ref, m0_ref,
            hs_ref, cf_ref, nf_ref, hf_ref, mf_ref,
            c_sc, n_sc, h_sc, m_sc, *, chunk: int, d: int):
    j = pl.program_id(1)
    nl = pl.num_programs(1)
    hd = d // _SLSTM_HEADS

    @pl.when(j == 0)
    def _load():
        c_sc[...] = c0_ref[...]
        n_sc[...] = n0_ref[...]
        h_sc[...] = h0_ref[...]
        m_sc[...] = m0_ref[...]

    def step(t, _):
        x_t = xg_ref[:, t, :]                         # (TB, 4D)
        hprev = h_sc[...]                             # (TB, D)
        # block-diagonal recurrence: per-head (TB, hd) @ (hd, 4hd)
        recs = []
        for h in range(_SLSTM_HEADS):
            hh = hprev[:, h * hd:(h + 1) * hd]
            recs.append(
                jnp.dot(hh, wr_ref[h], preferred_element_type=jnp.float32)
            )
        # reference wiring (models/xlstm.py::_slstm_step): head-major concat —
        # (B, H, 4hd).reshape(B, 4D) with 4hd == D
        rec = jnp.concatenate(recs, axis=-1)          # (TB, 4D)
        gates = x_t + rec + bias_ref[...]
        it = gates[:, :d]
        ft = gates[:, d:2 * d]
        zt = gates[:, 2 * d:3 * d]
        ot = gates[:, 3 * d:]
        log_f = -jnp.logaddexp(0.0, -ft)              # log sigmoid
        m_new = jnp.maximum(log_f + m_sc[...], it)
        i_sc = jnp.exp(it - m_new)
        f_sc = jnp.exp(log_f + m_sc[...] - m_new)
        c = f_sc * c_sc[...] + i_sc * jnp.tanh(zt)
        n = f_sc * n_sc[...] + i_sc
        h_new = jax.nn.sigmoid(ot) * c / jnp.maximum(n, 1e-6)
        c_sc[...] = c
        n_sc[...] = n
        m_sc[...] = m_new
        h_sc[...] = h_new
        hs_ref[:, t, :] = h_new
        return 0

    jax.lax.fori_loop(0, chunk, step, 0)

    @pl.when(j == nl - 1)
    def _final():
        cf_ref[...] = c_sc[...]
        nf_ref[...] = n_sc[...]
        hf_ref[...] = h_sc[...]
        mf_ref[...] = m_sc[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def slstm_scan(xg, wr, bias, c0, n0, h0, m0, *, chunk: int = 256,
               interpret: bool = False):
    """xg: (B, L, 4D) f32. Returns (hs (B, L, D), (c, n, h, m) final)."""
    b, l, d4 = xg.shape
    d = d4 // 4
    chunk = min(chunk, l)
    if l % chunk:
        raise ValueError(f"L={l} not divisible by chunk={chunk}")
    nl = l // chunk
    hd = d // _SLSTM_HEADS

    state_spec = pl.BlockSpec((b, d), lambda i, j: (0, 0))
    outs = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk, d=d),
        grid=(1, nl),
        in_specs=[
            pl.BlockSpec((b, chunk, d4), lambda i, j: (0, j, 0)),
            pl.BlockSpec((_SLSTM_HEADS, hd, 4 * hd), lambda i, j: (0, 0, 0)),
            pl.BlockSpec((d4,), lambda i, j: (0,)),
            state_spec, state_spec, state_spec, state_spec,
        ],
        out_specs=[
            pl.BlockSpec((b, chunk, d), lambda i, j: (0, j, 0)),
            state_spec, state_spec, state_spec, state_spec,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, l, d), jnp.float32),
            jax.ShapeDtypeStruct((b, d), jnp.float32),
            jax.ShapeDtypeStruct((b, d), jnp.float32),
            jax.ShapeDtypeStruct((b, d), jnp.float32),
            jax.ShapeDtypeStruct((b, d), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((b, d), jnp.float32),
            pltpu.VMEM((b, d), jnp.float32),
            pltpu.VMEM((b, d), jnp.float32),
            pltpu.VMEM((b, d), jnp.float32),
        ],
        interpret=interpret,
        name="repro_slstm_scan",
    )(
        xg.astype(jnp.float32), wr.astype(jnp.float32), bias.astype(jnp.float32),
        c0.astype(jnp.float32), n0.astype(jnp.float32),
        h0.astype(jnp.float32), m0.astype(jnp.float32),
    )
    hs, c, n, h, m = outs
    return hs, (c, n, h, m)


def hbm_traffic_estimate(b: int, l: int, d: int, kernel: bool) -> int:
    """Kernel: read xg + write hs once. XLA loop: + per-step carry r/w."""
    base = b * l * 4 * d * 4 + b * l * d * 4
    if kernel:
        return base
    per_step_carry = 4 * b * d * 4 * 2  # (c,n,h,m) written+read per step
    return base + l * per_step_carry
