"""Model stacks for every assigned family, assembled from the component layers.

All homogeneous stacks scan over stacked per-layer parameters
(``scan_layers=True``) with optional full per-layer remat — this keeps the
compiled HLO one-layer-sized, which is what makes 61-80 layer × 512-device
dry-runs compile quickly.

Families:
  dense / vlm      — pre-norm GQA + SwiGLU decoder (vlm prepends patch embeds)
  moe              — GQA or MLA attention + MoE FFN (leading dense layers opt.)
  hybrid (zamba2)  — Mamba2 stacks with ONE shared attention block re-invoked
                     every k layers (weight reuse; cf. the paper's BU reuse)
  ssm (xlstm)      — alternating mLSTM / sLSTM blocks
  audio (whisper)  — encoder (stub frame embeddings) + causal decoder w/ cross-attn
  spectral         — FNet-style: the paper's 2D FFT engine as the mixing layer
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.spectral import fourier_mixing
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models import xlstm as xlstm_mod
from repro.models.config import ModelConfig
from repro.models.layers import (
    embed,
    embedding_skel,
    mlp,
    mlp_skel,
    rmsnorm,
    rmsnorm_skel,
    unembed,
    unembed_skel,
)
from repro.models.param import ParamDef, stack_skeleton


def _maybe_remat(fn, cfg: ModelConfig):
    if not cfg.remat:
        return fn
    if cfg.remat_policy == "dots":
        # selective checkpointing: keep matmul outputs, recompute elementwise
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
    return jax.checkpoint(fn)


# ------------------------- decoder block (dense/moe) -------------------------

def decoder_block_skel(cfg: ModelConfig, use_moe: bool) -> dict:
    skel = {
        "ln1": rmsnorm_skel(cfg.d_model),
        "ln2": rmsnorm_skel(cfg.d_model),
    }
    if cfg.attention == "mla":
        skel["attn"] = attn.mla_skel(cfg)
    else:
        skel["attn"] = attn.gqa_skel(cfg)
    if use_moe:
        skel["moe"] = moe_mod.moe_skel(cfg)
    else:
        skel["mlp"] = mlp_skel(cfg.d_model, cfg.d_ff, cfg.act)
    return skel


def decoder_block_apply(
    p, x, cfg: ModelConfig, *, positions, cache=None, decode=False, ep_axis=None
):
    """Returns (x, new_cache, aux)."""
    h = rmsnorm(p["ln1"], x, cfg.rms_eps)
    if cfg.attention == "mla":
        a, new_cache = attn.mla_apply(
            p["attn"], h, cfg, positions=positions, cache=cache, decode=decode
        )
    else:
        a, new_cache = attn.gqa_apply(
            p["attn"], h, cfg, positions=positions, cache=cache, decode=decode
        )
    x = x + a
    h = rmsnorm(p["ln2"], x, cfg.rms_eps)
    if "moe" in p:
        f, aux = moe_mod.moe_apply(p["moe"], h, cfg, ep_axis=ep_axis)
    else:
        f, aux = mlp(p["mlp"], h, cfg.act), jnp.zeros((), jnp.float32)
    return x + f, new_cache, aux


def _scan_stack(block_fn, params_stacked, x, caches, n_layers: int, cfg: ModelConfig):
    """Scan a block over stacked params (+ optional stacked caches)."""

    def body(carry, layer_in):
        x, aux_sum = carry
        p_l, c_l = layer_in
        x, c_new, aux = block_fn(p_l, x, c_l)
        return (x, aux_sum + aux), c_new

    body = _maybe_remat(body, cfg)
    if caches is None:
        caches = jnp.zeros((n_layers,), jnp.float32)  # dummy xs
    (x, aux), new_caches = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)), (params_stacked, caches))
    return x, new_caches, aux


# ----------------------------- decoder-only LM -----------------------------

def lm_skel(cfg: ModelConfig) -> dict:
    n_dense = cfg.moe.n_dense_layers if cfg.moe else cfg.n_layers
    n_dense = min(n_dense, cfg.n_layers)
    n_moe = cfg.n_layers - n_dense if cfg.moe else 0
    skel: dict[str, Any] = {
        "embed": embedding_skel(cfg.vocab, cfg.d_model),
        "final_norm": rmsnorm_skel(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        skel["unembed"] = unembed_skel(cfg.vocab, cfg.d_model)
    if n_dense:
        skel["dense_layers"] = stack_skeleton(
            decoder_block_skel(cfg, use_moe=False), n_dense
        )
    if n_moe:
        skel["moe_layers"] = stack_skeleton(
            decoder_block_skel(cfg, use_moe=True), n_moe
        )
    if cfg.mtp:
        skel["mtp"] = {
            "norm_h": rmsnorm_skel(cfg.d_model),
            "norm_e": rmsnorm_skel(cfg.d_model),
            "proj": {
                "down": ParamDef((2 * cfg.d_model, cfg.d_model), ("mlp", "embed"))
            },
            "block": decoder_block_skel(cfg, use_moe=False),
        }
    return skel


def _logits(params, x, cfg):
    from repro.sharding.ctx import shard

    if cfg.tie_embeddings:
        logits = jnp.einsum(
            "bsd,vd->bsv", x, params["embed"]["table"].astype(x.dtype)
        ).astype(jnp.float32)
    else:
        logits = unembed(params["unembed"], x)
    return shard(logits, "dp", None, "tp")


def lm_forward(
    params,
    tokens,
    cfg: ModelConfig,
    *,
    pos0=0,
    caches=None,
    decode=False,
    prefill=False,
    ep_axis=None,
    prefix_embeds=None,
    return_hidden=False,
):
    """Shared forward for dense/moe/vlm LMs.

    Returns (logits, new_caches, aux[, hidden]). ``prefix_embeds`` (B, P, D)
    is the vlm stub frontend's patch embeddings, prepended to the tokens.
    """
    dt = jnp.dtype(cfg.compute_dtype)
    x = embed(params["embed"], tokens, dt)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(dt), x], axis=1)
    b, s, _ = x.shape
    positions = pos0 + jnp.arange(s, dtype=jnp.int32)[None, :]
    positions = jnp.broadcast_to(positions, (b, s))

    n_dense = cfg.moe.n_dense_layers if cfg.moe else cfg.n_layers
    n_dense = min(n_dense, cfg.n_layers)
    n_moe = cfg.n_layers - n_dense if cfg.moe else 0
    new_caches = {} if caches is not None else None
    aux_total = jnp.zeros((), jnp.float32)

    for name, n, use_moe in (
        ("dense_layers", n_dense, False),
        ("moe_layers", n_moe, True),
    ):
        if n == 0:
            continue

        def blk(p_l, x, c_l, use_moe=use_moe):
            c_in = c_l if caches is not None else None
            x, c_new, aux = decoder_block_apply(
                p_l, x, cfg,
                positions=positions, cache=c_in, decode=decode, ep_axis=ep_axis,
            )
            return x, (c_new if c_new is not None else jnp.zeros((), jnp.float32)), aux

        c_stack = caches[name] if caches is not None else None
        x, c_new, aux = _scan_stack(blk, params[name], x, c_stack, n, cfg)
        if caches is not None:
            new_caches[name] = c_new
        aux_total = aux_total + aux

    hidden = x
    x = rmsnorm(params["final_norm"], x, cfg.rms_eps)
    logits = _logits(params, x, cfg)
    if return_hidden:
        return logits, new_caches, aux_total, hidden
    return logits, new_caches, aux_total


def mtp_logits(params, hidden, tokens, cfg: ModelConfig):
    """DeepSeek-V3 multi-token prediction: predict t+2 from (h_t, emb_{t+1}).

    hidden: (B, S, D) pre-final-norm states. Returns logits (B, S-1, V)
    aligned so position t predicts tokens[t+2].
    """
    dt = jnp.dtype(cfg.compute_dtype)
    p = params["mtp"]
    h = rmsnorm(p["norm_h"], hidden[:, :-1], cfg.rms_eps)
    e = embed(params["embed"], tokens[:, 1:], dt)
    e = rmsnorm(p["norm_e"], e, cfg.rms_eps)
    x = jnp.einsum(
        "bsk,kd->bsd", jnp.concatenate([h, e], axis=-1), p["proj"]["down"].astype(dt)
    )
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    x, _, _ = decoder_block_apply(p["block"], x, cfg, positions=positions)
    x = rmsnorm(params["final_norm"], x, cfg.rms_eps)
    return _logits(params, x, cfg)


def lm_init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    n_dense = cfg.moe.n_dense_layers if cfg.moe else cfg.n_layers
    n_dense = min(n_dense, cfg.n_layers)
    n_moe = cfg.n_layers - n_dense if cfg.moe else 0

    def one(n):
        if cfg.attention == "mla":
            c = attn.make_mla_cache(cfg, batch, max_len, dtype)
        else:
            c = attn.make_cache(cfg, batch, max_len, dtype)
        return jax.tree.map(lambda a: jnp.broadcast_to(a, (n, *a.shape)), c)

    caches = {}
    if n_dense:
        caches["dense_layers"] = one(n_dense)
    if n_moe:
        caches["moe_layers"] = one(n_moe)
    return caches


# ------------------------------ hybrid (zamba2) ------------------------------

def hybrid_skel(cfg: ModelConfig) -> dict:
    """Mamba2 stack + ONE shared attention/MLP block over concat(x, x0)."""
    shared_cfg = _shared_block_cfg(cfg)
    return {
        "embed": embedding_skel(cfg.vocab, cfg.d_model),
        "final_norm": rmsnorm_skel(cfg.d_model),
        "unembed": unembed_skel(cfg.vocab, cfg.d_model),
        "mamba_layers": stack_skeleton(ssm_mod.mamba2_skel(cfg), cfg.n_layers),
        "shared": {
            "ln1": rmsnorm_skel(shared_cfg.d_model),
            "attn": attn.gqa_skel(shared_cfg),
            "ln2": rmsnorm_skel(shared_cfg.d_model),
            "mlp": mlp_skel(shared_cfg.d_model, cfg.d_ff, cfg.act),
            "proj": {
                "down": ParamDef(
                    (shared_cfg.d_model, cfg.d_model), ("mlp", "embed")
                )
            },
        },
    }


def _shared_block_cfg(cfg: ModelConfig) -> ModelConfig:
    return dataclasses.replace(
        cfg,
        d_model=2 * cfg.d_model,
        head_dim=2 * cfg.d_model // cfg.n_heads,
        attention="gqa",
    )


def _n_shared_invocations(cfg: ModelConfig) -> int:
    return max(1, cfg.n_layers // cfg.shared_attn_every)


def hybrid_forward(
    params, tokens, cfg: ModelConfig, *, pos0=0, caches=None, decode=False, **_
):
    """Returns (logits, new_caches, aux)."""
    dt = jnp.dtype(cfg.compute_dtype)
    x = embed(params["embed"], tokens, dt)
    x0 = x  # original embedding, re-fed to every shared-block invocation
    b, s, _ = x.shape
    positions = pos0 + jnp.arange(s, dtype=jnp.int32)[None, :]
    positions = jnp.broadcast_to(positions, (b, s))
    shared_cfg = _shared_block_cfg(cfg)

    n_inv = _n_shared_invocations(cfg)
    group = cfg.n_layers // n_inv
    new_caches: dict[str, Any] = {"mamba": [], "shared": []} if caches is not None else None
    mamba_stack = params["mamba_layers"]

    for gi in range(n_inv):
        sl = lambda a, gi=gi: jax.lax.slice_in_dim(a, gi * group, (gi + 1) * group, axis=0)
        p_group = jax.tree.map(sl, mamba_stack)

        def blk(p_l, x, c_l):
            st = c_l if caches is not None else None
            x_new, st_new = ssm_mod.mamba2_apply(p_l, x, cfg, state=st, decode=decode)
            return x_new, (st_new if st_new is not None else jnp.zeros((), jnp.float32)), jnp.zeros((), jnp.float32)

        c_group = (
            jax.tree.map(sl, caches["mamba"]) if caches is not None else None
        )
        x, c_new, _ = _scan_stack(blk, p_group, x, c_group, group, cfg)
        if caches is not None:
            new_caches["mamba"].append(c_new)

        # shared attention block (weights reused every invocation)
        xa = jnp.concatenate([x, x0], axis=-1)
        h = rmsnorm(params["shared"]["ln1"], xa, cfg.rms_eps)
        c_sh = (
            jax.tree.map(lambda a, gi=gi: a[gi], caches["shared"])
            if caches is not None
            else None
        )
        a_out, c_sh_new = attn.gqa_apply(
            params["shared"]["attn"], h, shared_cfg,
            positions=positions, cache=c_sh, decode=decode,
        )
        xa = xa + a_out
        h2 = rmsnorm(params["shared"]["ln2"], xa, cfg.rms_eps)
        xa = xa + mlp(params["shared"]["mlp"], h2, cfg.act)
        x = x + jnp.einsum(
            "bsk,kd->bsd", xa, params["shared"]["proj"]["down"].astype(dt)
        )
        if caches is not None:
            new_caches["shared"].append(c_sh_new)

    if caches is not None:
        new_caches["mamba"] = jax.tree.map(
            lambda *xs: jnp.concatenate(xs, axis=0), *new_caches["mamba"]
        )
        new_caches["shared"] = jax.tree.map(
            lambda *xs: jnp.stack(xs, axis=0), *new_caches["shared"]
        )

    x = rmsnorm(params["final_norm"], x, cfg.rms_eps)
    return unembed(params["unembed"], x), new_caches, jnp.zeros((), jnp.float32)


def hybrid_init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    n_inv = _n_shared_invocations(cfg)
    shared_cfg = _shared_block_cfg(cfg)
    st = ssm_mod.mamba2_state(cfg, batch)
    kv = attn.make_cache(shared_cfg, batch, max_len, dtype)
    return {
        "mamba": jax.tree.map(
            lambda a: jnp.broadcast_to(a, (cfg.n_layers, *a.shape)), st
        ),
        "shared": jax.tree.map(lambda a: jnp.broadcast_to(a, (n_inv, *a.shape)), kv),
    }


# -------------------------------- ssm (xlstm) --------------------------------

def xlstm_skel(cfg: ModelConfig) -> dict:
    n_pairs = cfg.n_layers // 2
    return {
        "embed": embedding_skel(cfg.vocab, cfg.d_model),
        "final_norm": rmsnorm_skel(cfg.d_model),
        "unembed": unembed_skel(cfg.vocab, cfg.d_model),
        "mlstm_layers": stack_skeleton(xlstm_mod.mlstm_skel(cfg), n_pairs),
        "slstm_layers": stack_skeleton(xlstm_mod.slstm_skel(cfg), n_pairs),
    }


def xlstm_forward(
    params, tokens, cfg: ModelConfig, *, pos0=0, caches=None, decode=False, **_
):
    dt = jnp.dtype(cfg.compute_dtype)
    x = embed(params["embed"], tokens, dt)
    n_pairs = cfg.n_layers // 2

    def blk(p_pair, x, c_pair):
        pm, ps = p_pair
        cm = c_pair[0] if caches is not None else None
        cs = c_pair[1] if caches is not None else None
        dm, sm = xlstm_mod.mlstm_apply(pm, rmsnorm_like(x, cfg), cfg, state=cm, decode=decode)
        x = x + dm
        ds, ss = xlstm_mod.slstm_apply(ps, rmsnorm_like(x, cfg), cfg, state=cs, decode=decode)
        x = x + ds
        zero = jnp.zeros((), jnp.float32)
        return x, ((sm if sm is not None else zero), (ss if ss is not None else zero)), zero

    c_stack = (
        (caches["mlstm"], caches["slstm"]) if caches is not None else None
    )
    x, c_new, _ = _scan_stack(
        blk, (params["mlstm_layers"], params["slstm_layers"]), x, c_stack, n_pairs, cfg
    )
    new_caches = (
        {"mlstm": c_new[0], "slstm": c_new[1]} if caches is not None else None
    )
    x = rmsnorm(params["final_norm"], x, cfg.rms_eps)
    return unembed(params["unembed"], x), new_caches, jnp.zeros((), jnp.float32)


def rmsnorm_like(x, cfg):
    """Parameter-free pre-norm used inside the xLSTM residual blocks
    (the blocks carry their own learned norms internally)."""
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + cfg.rms_eps).astype(x.dtype))


def xlstm_init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.float32):
    n_pairs = cfg.n_layers // 2
    sm = xlstm_mod.mlstm_state(cfg, batch)
    ss = xlstm_mod.slstm_state(cfg, batch)
    return {
        "mlstm": jax.tree.map(lambda a: jnp.broadcast_to(a, (n_pairs, *a.shape)), sm),
        "slstm": jax.tree.map(lambda a: jnp.broadcast_to(a, (n_pairs, *a.shape)), ss),
    }


# ------------------------------ audio (whisper) ------------------------------

def encdec_skel(cfg: ModelConfig) -> dict:
    enc_block = {
        "ln1": rmsnorm_skel(cfg.d_model),
        "attn": attn.gqa_skel(cfg),
        "ln2": rmsnorm_skel(cfg.d_model),
        "mlp": mlp_skel(cfg.d_model, cfg.d_ff, "gelu"),
    }
    dec_block = {
        "ln1": rmsnorm_skel(cfg.d_model),
        "attn": attn.gqa_skel(cfg),
        "lnx": rmsnorm_skel(cfg.d_model),
        "xattn": attn.cross_attn_skel(cfg),
        "ln2": rmsnorm_skel(cfg.d_model),
        "mlp": mlp_skel(cfg.d_model, cfg.d_ff, "gelu"),
    }
    return {
        "embed": embedding_skel(cfg.vocab, cfg.d_model),
        "enc_norm": rmsnorm_skel(cfg.d_model),
        "final_norm": rmsnorm_skel(cfg.d_model),
        "unembed": unembed_skel(cfg.vocab, cfg.d_model),
        "enc_layers": stack_skeleton(enc_block, cfg.n_enc_layers or cfg.n_layers),
        "dec_layers": stack_skeleton(dec_block, cfg.n_layers),
    }


def encoder_forward(params, frames, cfg: ModelConfig):
    """frames: (B, T, D) precomputed stub embeddings (assignment-mandated)."""
    dt = jnp.dtype(cfg.compute_dtype)
    x = frames.astype(dt)
    b, t, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None], (b, t))

    def blk(p_l, x, _c):
        h = rmsnorm(p_l["ln1"], x, cfg.rms_eps)
        a, _ = attn.gqa_apply(p_l["attn"], h, cfg, positions=positions, causal=False)
        x = x + a
        h = rmsnorm(p_l["ln2"], x, cfg.rms_eps)
        return x + mlp(p_l["mlp"], h, "gelu"), jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)

    n_enc = cfg.n_enc_layers or cfg.n_layers
    x, _, _ = _scan_stack(blk, params["enc_layers"], x, None, n_enc, cfg)
    return rmsnorm(params["enc_norm"], x, cfg.rms_eps)


def encdec_forward(
    params, tokens, cfg: ModelConfig, *,
    frames=None, enc_out=None, pos0=0, caches=None, decode=False, **_,
):
    """Decoder forward. Returns (logits, new_caches, aux). During decode the
    per-layer cross-attention K/V live in the cache (computed at prefill)."""
    dt = jnp.dtype(cfg.compute_dtype)
    if enc_out is None and frames is not None:
        enc_out = encoder_forward(params, frames, cfg)
    x = embed(params["embed"], tokens, dt)
    b, s, _ = x.shape
    positions = pos0 + jnp.arange(s, dtype=jnp.int32)[None, :]
    positions = jnp.broadcast_to(positions, (b, s))

    def blk(p_l, x, c_l):
        c_self = c_l["self"] if caches is not None else None
        h = rmsnorm(p_l["ln1"], x, cfg.rms_eps)
        a, c_self_new = attn.gqa_apply(
            p_l["attn"], h, cfg, positions=positions, cache=c_self, decode=decode
        )
        x = x + a
        h = rmsnorm(p_l["lnx"], x, cfg.rms_eps)
        if caches is not None:
            if decode:
                kx, vx = c_l["cross_k"], c_l["cross_v"]
            else:
                kx, vx = attn.cross_kv(p_l["xattn"], enc_out, dt)
        else:
            kx, vx = attn.cross_kv(p_l["xattn"], enc_out, dt)
        x = x + attn.cross_attn_apply(p_l["xattn"], h, (kx, vx), cfg)
        h = rmsnorm(p_l["ln2"], x, cfg.rms_eps)
        x = x + mlp(p_l["mlp"], h, "gelu")
        zero = jnp.zeros((), jnp.float32)
        if caches is not None:
            return x, {"self": c_self_new, "cross_k": kx, "cross_v": vx}, zero
        return x, zero, zero

    c_stack = caches["dec"] if caches is not None else None
    x, c_new, _ = _scan_stack(blk, params["dec_layers"], x, c_stack, cfg.n_layers, cfg)
    new_caches = {"dec": c_new} if caches is not None else None
    x = rmsnorm(params["final_norm"], x, cfg.rms_eps)
    return unembed(params["unembed"], x), new_caches, jnp.zeros((), jnp.float32)


def encdec_init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    kv = attn.make_cache(cfg, batch, max_len, dtype)
    h, dh = cfg.n_heads, cfg.resolved_head_dim
    cross = {
        "cross_k": jnp.zeros((batch, cfg.enc_frames, h, dh), dtype),
        "cross_v": jnp.zeros((batch, cfg.enc_frames, h, dh), dtype),
    }
    per_layer = {"self": kv, **cross}
    return {
        "dec": jax.tree.map(
            lambda a: jnp.broadcast_to(a, (cfg.n_layers, *a.shape)), per_layer
        )
    }


# ----------------------------- spectral (fourier) -----------------------------

def spectral_skel(cfg: ModelConfig) -> dict:
    block = {
        "ln1": rmsnorm_skel(cfg.d_model),
        "ln2": rmsnorm_skel(cfg.d_model),
        "mlp": mlp_skel(cfg.d_model, cfg.d_ff, "gelu"),
    }
    return {
        "embed": embedding_skel(cfg.vocab, cfg.d_model),
        "final_norm": rmsnorm_skel(cfg.d_model),
        "unembed": unembed_skel(cfg.vocab, cfg.d_model),
        "layers": stack_skeleton(block, cfg.n_layers),
    }


def spectral_forward(params, tokens, cfg: ModelConfig, **_):
    """FNet-style encoder LM: mixing = Re(FFT2) — the paper's engine."""
    dt = jnp.dtype(cfg.compute_dtype)
    x = embed(params["embed"], tokens, dt)

    def blk(p_l, x, _c):
        h = rmsnorm(p_l["ln1"], x, cfg.rms_eps)
        x = x + fourier_mixing(h, variant=cfg.fft_variant)
        h = rmsnorm(p_l["ln2"], x, cfg.rms_eps)
        return x + mlp(p_l["mlp"], h, "gelu"), jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)

    x, _, _ = _scan_stack(blk, params["layers"], x, None, cfg.n_layers, cfg)
    x = rmsnorm(params["final_norm"], x, cfg.rms_eps)
    return unembed(params["unembed"], x), None, jnp.zeros((), jnp.float32)
