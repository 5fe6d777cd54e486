"""Pallas TPU flash-attention kernel (beyond-paper optimization).

Motivation (EXPERIMENTS.md §Perf): the dry-run shows attention-heavy cells
memory-dominated by materialised (block_q × S) score traffic — XLA cannot
fuse dot→softmax→dot. This kernel keeps scores in VMEM: per (batch·head,
q-block) the online-softmax accumulator persists across the kv-block grid
dimension, so HBM traffic drops from O(S²·H) to O(S·H·D) per layer —
the same VMEM-residency insight the paper's butterfly reuse embodies,
applied to attention.

Layout: q (BH, Sq, D), k/v (BH, Sk, D) float32 (complex-free ABI like the
FFT kernels; GQA callers pre-map heads). Grid: (BH, nq, nk) with the kv
dimension innermost ("arbitrary" semantics) and VMEM scratch carrying
(acc, m, l) across kv steps.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1.0e30

__all__ = ["flash_attention_fwd", "mha_reference"]


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            causal: bool, window: int | None, block_q: int, block_k: int,
            sq: int, sk: int):
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0]  # (bq, d)
    k = k_ref[0]  # (bk, d)
    v = v_ref[0]
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
    s = s * (1.0 / math.sqrt(q.shape[-1]))

    qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    kpos = kj * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = kpos < sk
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    l_prev = l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jnp.dot(
        p, v, preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(kj == nk - 1)
    def _finalize():
        o_ref[0] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-20)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k", "interpret"),
)
def flash_attention_fwd(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    block_q: int = 256,
    block_k: int = 512,
    interpret: bool = False,
):
    """q: (BH, Sq, D); k, v: (BH, Sk, D) — float32. Returns (BH, Sq, D)."""
    bh, sq, d = q.shape
    _, sk, dv = v.shape
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    pad_q = (-sq) % block_q
    pad_k = (-sk) % block_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0)))
    nq = (sq + pad_q) // block_q
    nk = (sk + pad_k) // block_k

    out = pl.pallas_call(
        functools.partial(
            _kernel, causal=causal, window=window,
            block_q=block_q, block_k=block_k, sq=sq, sk=sk,
        ),
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, dv), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, dv), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq + pad_q, dv), jnp.float32),
        scratch_shapes=[
            # (acc, m, l) persist across the kv grid dimension in VMEM
            pltpu.VMEM((block_q, dv), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
        name="repro_flash_attention_fwd",
    )(q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32))
    return out[:, :sq]


def mha_reference(q, k, v, *, causal=True, window=None):
    """Naive oracle: (BH, Sq, D) × (BH, Sk, D)."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    s = jnp.einsum("bqd,bkd->bqk", q, k) / math.sqrt(d)
    qpos = jnp.arange(sq)[:, None]
    kpos = jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask[None], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", w, v)
