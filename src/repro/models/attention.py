"""Attention: chunked online-softmax (flash-style), GQA, MLA, SWA + caches.

Pure JAX with static block sizes — the memory-safe formulation the dry-run
needs (never materialises an (S, S) score matrix). Decode paths score one
query against a cache: dense buffer for full attention, ring buffer (size =
window) for sliding-window attention, compressed-latent buffer for MLA
(absorbed decode — the (B, S, r) latent is never expanded per head).
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.models.config import MLAConfig, ModelConfig
from repro.models.layers import apply_rope
from repro.models.param import ParamDef
from repro.sharding.ctx import cp_axis_for, shard, tp_size

NEG_INF = -1.0e30


# ------------------------- flash attention -------------------------

def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    q_offset: int = 0,
    block_q: int = 512,
    block_k: int = 1024,
    vma_axes: tuple = (),
) -> jax.Array:
    """q: (B, Sq, H, Dk); k: (B, Sk, KV, Dk); v: (B, Sk, KV, Dv). GQA via H=KV·g.

    Online-softmax over KV blocks inside a map over Q blocks — peak score
    memory is (B, bq, H, bk) regardless of sequence length.
    """
    b, sq0, h, dk = q.shape
    _, sk0, kv, _ = k.shape
    dv = v.shape[-1]
    g = h // kv
    block_q = min(block_q, sq0)
    block_k = min(block_k, sk0)
    # Pad ragged tails; padded k positions are masked out, padded q rows dropped.
    pad_q = (-sq0) % block_q
    pad_k = (-sk0) % block_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    sq, sk = sq0 + pad_q, sk0 + pad_k
    nq, nk = sq // block_q, sk // block_k
    scale = 1.0 / math.sqrt(dk)

    qb = q.reshape(b, nq, block_q, kv, g, dk)
    kb = k.reshape(b, nk, block_k, kv, dk)
    vb = v.reshape(b, nk, block_k, kv, dv)

    def q_block(i):
        qi = qb[:, i] * scale  # (b, bq, kv, g, dk)
        qpos = q_offset + i * block_q + jnp.arange(block_q)

        def kv_step(carry, j):
            acc, m, l = carry
            kj = kb[:, j]
            vj = vb[:, j]
            s = jnp.einsum(
                "bqhgd,bkhd->bqhgk", qi, kj, preferred_element_type=jnp.float32
            )
            kpos = j * block_k + jnp.arange(block_k)
            mask = jnp.broadcast_to(kpos[None, :] < sk0, (block_q, block_k))
            if causal:
                mask &= kpos[None, :] <= qpos[:, None]
            if window is not None:
                mask &= kpos[None, :] > qpos[:, None] - window
            s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bqhgk,bkhd->bqhgd", p.astype(vj.dtype), vj,
                preferred_element_type=jnp.float32,
            )
            return (acc_new, m_new, l_new), None

        acc0 = jnp.zeros((b, block_q, kv, g, dv), jnp.float32)
        m0 = jnp.full((b, block_q, kv, g), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, block_q, kv, g), jnp.float32)
        if vma_axes:  # inside shard_map: mark carries as manual-varying
            acc0, m0, l0 = (
                jax.lax.pcast(t, vma_axes, to="varying") for t in (acc0, m0, l0)
            )
        (acc, _, l), _ = jax.lax.scan(
            jax.checkpoint(kv_step), (acc0, m0, l0), jnp.arange(nk)
        )
        return (acc / jnp.maximum(l[..., None], 1e-20)).astype(q.dtype)

    out = jax.lax.map(q_block, jnp.arange(nq))  # (nq, b, bq, kv, g, dv)
    out = jnp.moveaxis(out, 0, 1)  # (b, nq, bq, kv, g, dv)
    return out.reshape(b, sq, h, dv)[:, :sq0]


def flash_attention_cp(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis: str,
    **kw,
) -> jax.Array:
    """Context-parallel flash attention: Q sequence-sharded over ``axis``,
    K/V replicated across it (each rank attends its query slice against the
    full keys). Used when an arch can neither head-TP nor 2-D-batch its
    attention for the given batch (§Perf cell B) — e.g. llama/starcoder
    prefill_32k, whose batch of 32 leaves the model axis idle."""
    import functools as _ft

    from jax.sharding import PartitionSpec as P

    mesh = jax.sharding.get_abstract_mesh()

    @_ft.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(None, axis, None, None), P(), P()),
        out_specs=P(None, axis, None, None),
        axis_names={axis},
    )
    def run(q_loc, k_full, v_full):
        rank = jax.lax.axis_index(axis)
        off = rank * q_loc.shape[1]
        return flash_attention(
            q_loc, k_full, v_full, q_offset=off, vma_axes=(axis,), **kw
        )

    return run(q, k, v)


def decode_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    slot_pos: jax.Array,
    cur_pos: jax.Array,
) -> jax.Array:
    """One-token attention over a cache buffer.

    q: (B, 1, H, Dk); caches (B, S, KV, D*); slot_pos (S,) giving the global
    position stored in each slot (−1 = empty) — valid for both dense caches
    (slot_pos = arange) and SWA ring caches (rotating slots).
    """
    b, _, h, dk = q.shape
    kv = k_cache.shape[2]
    g = h // kv
    scale = 1.0 / math.sqrt(dk)
    qh = q.reshape(b, kv, g, dk) * scale
    s = jnp.einsum(
        "bhgd,bshd->bhgs", qh, k_cache, preferred_element_type=jnp.float32
    )
    valid = (slot_pos >= 0) & (slot_pos <= cur_pos)
    s = jnp.where(valid[None, None, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum(
        "bhgs,bshd->bhgd", w.astype(v_cache.dtype), v_cache,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(b, 1, h, v_cache.shape[-1]).astype(q.dtype)


# ----------------------------- GQA layer -----------------------------

def gqa_skel(cfg: ModelConfig) -> dict:
    d, h, kv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    return {
        "wq": ParamDef((d, h, dh), ("embed", "heads", "head_dim")),
        "wk": ParamDef((d, kv, dh), ("embed", "kv_heads", "head_dim")),
        "wv": ParamDef((d, kv, dh), ("embed", "kv_heads", "head_dim")),
        "wo": ParamDef((h, dh, d), ("heads", "head_dim", "embed")),
    }


def make_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    """Dense or ring (SWA) KV cache for one layer."""
    size = min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len
    kv, dh = cfg.n_kv_heads, cfg.resolved_head_dim
    return {
        "k": jnp.zeros((batch, size, kv, dh), dtype),
        "v": jnp.zeros((batch, size, kv, dh), dtype),
        "slot_pos": jnp.full((size,), -1, jnp.int32),
    }


def _cache_insert(cache: dict, k_new: jax.Array, v_new: jax.Array, pos: jax.Array):
    """Insert (B, S_new, KV, Dh) at global position ``pos`` (ring-aware)."""
    size = cache["k"].shape[1]
    s_new = k_new.shape[1]
    if s_new == 1:
        slot = (pos % size).astype(jnp.int32)
        k = jax.lax.dynamic_update_slice(cache["k"], k_new.astype(cache["k"].dtype), (0, slot, 0, 0))
        v = jax.lax.dynamic_update_slice(cache["v"], v_new.astype(cache["v"].dtype), (0, slot, 0, 0))
        sp = jax.lax.dynamic_update_slice(cache["slot_pos"], pos[None].astype(jnp.int32), (slot,))
    else:
        # prefill: keep the last ``size`` entries (ring) or all (dense)
        take = min(s_new, size)
        k_tail = k_new[:, s_new - take :]
        v_tail = v_new[:, s_new - take :]
        k = jax.lax.dynamic_update_slice(cache["k"], k_tail.astype(cache["k"].dtype), (0, 0, 0, 0))
        v = jax.lax.dynamic_update_slice(cache["v"], v_tail.astype(cache["v"].dtype), (0, 0, 0, 0))
        sp = jnp.where(
            jnp.arange(size) < take,
            jnp.arange(size, dtype=jnp.int32) + (s_new - take),
            cache["slot_pos"],
        )
    return {"k": k, "v": v, "slot_pos": sp}


def gqa_apply(
    p: dict,
    x: jax.Array,
    cfg: ModelConfig,
    *,
    positions: jax.Array,
    causal: bool = True,
    cache: dict | None = None,
    decode: bool = False,
):
    """Returns (out, new_cache). x: (B, S, D)."""
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(dt))
    # TP over heads when the head count divides the model axis; otherwise
    # 2-D batch parallelism (batch over data×model) keeps attention
    # collective-free for the 24/48/4-head archs.
    if not decode:
        heads_tp = q.shape[2] % tp_size() == 0
        bt = "dp" if heads_tp else "dp+tp"
        ht = "tp" if heads_tp else None
        q = shard(q, bt, None, ht, None)
        k = shard(k, bt, None, ht, None)
        v = shard(v, bt, None, ht, None)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)

    new_cache = None
    if decode:
        assert cache is not None
        pos = positions[0, 0] if positions.ndim == 2 else positions[0]
        new_cache = _cache_insert(cache, k, v, pos)
        out = decode_attention(q, new_cache["k"], new_cache["v"], new_cache["slot_pos"], pos)
    else:
        cp = cp_axis_for(q.shape[0], q.shape[1])
        if cp is not None and q.shape[1] == k.shape[1]:
            out = flash_attention_cp(
                q, k, v, cp,
                causal=causal,
                window=cfg.sliding_window,
                block_q=cfg.attn_block_q,
                block_k=cfg.attn_block_k,
            )
        else:
            out = flash_attention(
                q, k, v,
                causal=causal,
                window=cfg.sliding_window,
                block_q=cfg.attn_block_q,
                block_k=cfg.attn_block_k,
            )
        if cache is not None:
            pos = positions[0, 0] if positions.ndim == 2 else positions[0]
            new_cache = _cache_insert(cache, k, v, pos)
        heads_tp = out.shape[2] % tp_size() == 0
        out = shard(out, "dp" if heads_tp else "dp+tp", None,
                    "tp" if heads_tp else None, None)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dt))
    return shard(y, "dp", None, None), new_cache


# ------------------------- cross attention -------------------------

def cross_attn_skel(cfg: ModelConfig) -> dict:
    d, h, dh = cfg.d_model, cfg.n_heads, cfg.resolved_head_dim
    return {
        "wq": ParamDef((d, h, dh), ("embed", "heads", "head_dim")),
        "wk": ParamDef((d, h, dh), ("embed", "heads", "head_dim")),
        "wv": ParamDef((d, h, dh), ("embed", "heads", "head_dim")),
        "wo": ParamDef((h, dh, d), ("heads", "head_dim", "embed")),
    }


def cross_attn_apply(p, x, enc_kv: tuple[jax.Array, jax.Array] | jax.Array, cfg):
    """x: (B, S, D); enc_kv: precomputed (k, v) or encoder output (B, T, D)."""
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    if isinstance(enc_kv, tuple):
        k, v = enc_kv
    else:
        k = jnp.einsum("btd,dhk->bthk", enc_kv, p["wk"].astype(dt))
        v = jnp.einsum("btd,dhk->bthk", enc_kv, p["wv"].astype(dt))
    out = flash_attention(
        q, k, v, causal=False,
        block_q=cfg.attn_block_q, block_k=min(cfg.attn_block_k, k.shape[1]),
    )
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dt))


def cross_kv(p, enc_out, dtype):
    k = jnp.einsum("btd,dhk->bthk", enc_out.astype(dtype), p["wk"].astype(dtype))
    v = jnp.einsum("btd,dhk->bthk", enc_out.astype(dtype), p["wv"].astype(dtype))
    return k, v


# ------------------------------- MLA -------------------------------

def mla_skel(cfg: ModelConfig) -> dict:
    m: MLAConfig = cfg.mla
    d, h = cfg.d_model, cfg.n_heads
    dq = m.qk_nope_head_dim + m.qk_rope_head_dim
    return {
        "wq_a": ParamDef((d, m.q_lora_rank), ("embed", "q_lora")),
        "q_norm": ParamDef((m.q_lora_rank,), ("q_lora",), init="ones"),
        "wq_b": ParamDef((m.q_lora_rank, h, dq), ("q_lora", "heads", "head_dim")),
        "wkv_a": ParamDef(
            (d, m.kv_lora_rank + m.qk_rope_head_dim), ("embed", "kv_lora")
        ),
        "kv_norm": ParamDef((m.kv_lora_rank,), ("kv_lora",), init="ones"),
        "wk_b": ParamDef(
            (m.kv_lora_rank, h, m.qk_nope_head_dim), ("kv_lora", "heads", "head_dim")
        ),
        "wv_b": ParamDef(
            (m.kv_lora_rank, h, m.v_head_dim), ("kv_lora", "heads", "head_dim")
        ),
        "wo": ParamDef((h, m.v_head_dim, d), ("heads", "head_dim", "embed")),
    }


def make_mla_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    m = cfg.mla
    return {
        "c_kv": jnp.zeros((batch, max_len, m.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, max_len, m.qk_rope_head_dim), dtype),
        "slot_pos": jnp.full((max_len,), -1, jnp.int32),
    }


def _rms(x, w, eps=1e-5):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps).astype(x.dtype)) * w.astype(x.dtype)


def mla_apply(
    p: dict,
    x: jax.Array,
    cfg: ModelConfig,
    *,
    positions: jax.Array,
    cache: dict | None = None,
    decode: bool = False,
):
    """DeepSeek Multi-head Latent Attention. Returns (out, new_cache)."""
    m: MLAConfig = cfg.mla
    dt = x.dtype
    b, s, _ = x.shape
    nope, drope = m.qk_nope_head_dim, m.qk_rope_head_dim

    q = jnp.einsum(
        "bsr,rhk->bshk", _rms(jnp.einsum("bsd,dr->bsr", x, p["wq_a"].astype(dt)), p["q_norm"]),
        p["wq_b"].astype(dt),
    )
    if not decode:
        q = shard(q, "dp", None, "tp", None)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    ckv_full = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"].astype(dt))
    c_kv = _rms(ckv_full[..., : m.kv_lora_rank], p["kv_norm"])
    k_rope = ckv_full[..., m.kv_lora_rank :]
    k_rope = apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta)[:, :, 0, :]

    scale = 1.0 / math.sqrt(nope + drope)
    new_cache = None

    if decode:
        assert cache is not None
        pos = positions[0, 0] if positions.ndim == 2 else positions[0]
        size = cache["c_kv"].shape[1]
        slot = (pos % size).astype(jnp.int32)
        c_buf = jax.lax.dynamic_update_slice(
            cache["c_kv"], c_kv.astype(cache["c_kv"].dtype), (0, slot, 0)
        )
        r_buf = jax.lax.dynamic_update_slice(
            cache["k_rope"], k_rope.astype(cache["k_rope"].dtype), (0, slot, 0)
        )
        sp = jax.lax.dynamic_update_slice(
            cache["slot_pos"], pos[None].astype(jnp.int32), (slot,)
        )
        new_cache = {"c_kv": c_buf, "k_rope": r_buf, "slot_pos": sp}
        # Absorbed decode: never expand per-head K/V from the latent.
        q_abs = jnp.einsum("bshk,rhk->bshr", q_nope, p["wk_b"].astype(dt))
        s_lat = jnp.einsum("bshr,btr->bhst", q_abs, c_buf.astype(dt))
        s_rope = jnp.einsum("bshk,btk->bhst", q_rope, r_buf.astype(dt))
        logits = (s_lat + s_rope).astype(jnp.float32) * scale
        valid = (sp >= 0) & (sp <= pos)
        logits = jnp.where(valid[None, None, None, :], logits, NEG_INF)
        w = jax.nn.softmax(logits, axis=-1).astype(dt)
        o_lat = jnp.einsum("bhst,btr->bshr", w, c_buf.astype(dt))
        out = jnp.einsum("bshr,rhv->bshv", o_lat, p["wv_b"].astype(dt))
    else:
        k_nope = shard(
            jnp.einsum("bsr,rhk->bshk", c_kv, p["wk_b"].astype(dt)),
            "dp", None, "tp", None,
        )
        v = shard(
            jnp.einsum("bsr,rhv->bshv", c_kv, p["wv_b"].astype(dt)),
            "dp", None, "tp", None,
        )
        k_full = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (b, s, cfg.n_heads, drope))],
            axis=-1,
        )
        q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
        out = flash_attention(
            q_full, k_full, v,
            causal=True,
            block_q=cfg.attn_block_q, block_k=cfg.attn_block_k,
        )
        if cache is not None:
            size = cache["c_kv"].shape[1]
            take = min(s, size)
            c_buf = jax.lax.dynamic_update_slice(
                cache["c_kv"], c_kv[:, -take:].astype(cache["c_kv"].dtype), (0, 0, 0)
            )
            r_buf = jax.lax.dynamic_update_slice(
                cache["k_rope"], k_rope[:, -take:].astype(cache["k_rope"].dtype), (0, 0, 0)
            )
            sp = jnp.where(
                jnp.arange(size) < take,
                jnp.arange(size, dtype=jnp.int32) + (s - take),
                cache["slot_pos"],
            )
            new_cache = {"c_kv": c_buf, "k_rope": r_buf, "slot_pos": sp}
        out = shard(out, "dp", None, "tp", None)

    y = jnp.einsum("bshv,hvd->bsd", out, p["wo"].astype(dt))
    return shard(y, "dp", None, None), new_cache
