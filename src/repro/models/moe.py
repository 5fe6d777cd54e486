"""Mixture-of-Experts: token-choice top-k routing with three dispatch paths.

  * ``dense_small``   — every expert on every token (tiny E, smoke tests).
  * ``grouped_local`` — capacity-grouped batched matmul per batch row; no
    cross-device dispatch (experts replicated/FSDP over data, hidden TP over
    model). The paper-faithful-baseline path for the MoE archs.
  * ``ep_a2a``        — expert parallelism: experts sharded over the data
    axis, tokens exchanged with all_to_all (beyond-paper optimization for
    the collective-bound cells; see EXPERIMENTS.md §Perf).

All paths share the router and the (E, D, F) expert weight layout, drop
over-capacity tokens (standard dropped-token semantics), and return an
auxiliary load-balance loss.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig, MoEConfig
from repro.models.param import ParamDef
from repro.sharding.ctx import shard


def moe_skel(cfg: ModelConfig) -> dict:
    m: MoEConfig = cfg.moe
    d, f, e = cfg.d_model, m.d_ff_expert, m.n_experts
    skel = {
        "router": ParamDef((d, e), ("embed", "experts")),
        "wg": ParamDef((e, d, f), ("experts", "embed", "mlp")),
        "wu": ParamDef((e, d, f), ("experts", "embed", "mlp")),
        "wd": ParamDef((e, f, d), ("experts", "mlp", "embed")),
    }
    if m.n_shared_experts:
        fs = f * m.n_shared_experts
        skel["shared"] = {
            "wg": ParamDef((d, fs), ("embed", "mlp")),
            "wu": ParamDef((d, fs), ("embed", "mlp")),
            "wd": ParamDef((fs, d), ("mlp", "embed")),
        }
    return skel


def _router(p, x, m: MoEConfig):
    """Returns (gates (..., k), expert_ids (..., k) int32, aux_loss scalar)."""
    logits = jnp.einsum(
        "...d,de->...e", x.astype(jnp.float32), p["router"].astype(jnp.float32)
    )
    if m.router_norm == "sigmoid":  # deepseek-v3 style
        scores = jax.nn.sigmoid(logits)
        gates, ids = jax.lax.top_k(scores, m.top_k)
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    else:  # mixtral style: softmax over the selected logits
        top_logits, ids = jax.lax.top_k(logits, m.top_k)
        gates = jax.nn.softmax(top_logits, axis=-1)
    # Switch-style load-balance aux: E * sum_e f_e * p_e
    probs = jax.nn.softmax(logits, axis=-1)
    e = logits.shape[-1]
    frac = jnp.mean(
        jnp.sum(jax.nn.one_hot(ids, e, dtype=jnp.float32), axis=-2),
        axis=tuple(range(ids.ndim - 1)),
    )  # fraction routed per expert (×k)
    mean_prob = jnp.mean(probs, axis=tuple(range(probs.ndim - 1)))
    aux = e * jnp.sum(frac / m.top_k * mean_prob)
    return gates.astype(x.dtype), ids.astype(jnp.int32), aux


def _expert_ffn(wg, wu, wd, h, act: str = "swiglu"):
    """h: (E, C, D) grouped tokens; per-expert FFN (ep_a2a path, shard_map)."""
    dt = h.dtype
    g = jnp.einsum("ecd,edf->ecf", h, wg.astype(dt))
    u = jnp.einsum("ecd,edf->ecf", h, wu.astype(dt))
    a = jax.nn.silu(g) * u if act == "swiglu" else jax.nn.gelu(g) * u
    return jnp.einsum("ecf,efd->ecd", a, wd.astype(dt))


def _expert_ffn_batched(wg, wu, wd, h, act: str = "swiglu"):
    """h: (B, E, C, D); batch stays dp-sharded, expert hidden is TP'd."""
    dt = h.dtype
    g = shard(jnp.einsum("becd,edf->becf", h, wg.astype(dt)), "dp", None, None, "tp")
    u = shard(jnp.einsum("becd,edf->becf", h, wu.astype(dt)), "dp", None, None, "tp")
    a = jax.nn.silu(g) * u if act == "swiglu" else jax.nn.gelu(g) * u
    y = jnp.einsum("becf,efd->becd", a, wd.astype(dt))
    return shard(y, "dp", None, None, None)


def _group_by_expert(ids_flat: jax.Array, n_experts: int, capacity: int):
    """Sort assignment slots by expert; compute each slot's position in its
    expert group (without materialising an (A, E) cumsum).

    Returns (order, slot, keep): ``order`` sorts assignments by expert,
    ``slot`` is the flat (e*C + pos) destination (clipped), ``keep`` masks
    assignments that fit under capacity.
    """
    a = ids_flat.shape[0]
    order = jnp.argsort(ids_flat, stable=True)
    sorted_ids = ids_flat[order]
    idx = jnp.arange(a, dtype=jnp.int32)
    is_start = jnp.concatenate(
        [jnp.ones((1,), bool), sorted_ids[1:] != sorted_ids[:-1]]
    )
    seg_start = jax.lax.associative_scan(jnp.maximum, jnp.where(is_start, idx, 0))
    pos = idx - seg_start
    keep = pos < capacity
    slot = sorted_ids * capacity + jnp.minimum(pos, capacity - 1)
    return order, slot, keep


def _moe_grouped_rows(p, x, m: MoEConfig, act: str):
    """Per-batch-row capacity grouping, explicitly batched (vmap-free so the
    sharding constraints apply). x: (B, S, D) -> (B, S, D)."""
    b, s, d = x.shape
    gates, ids, aux = _router(p, x, m)
    k = m.top_k
    e = m.n_experts
    capacity = max(1, int(s * k / e * m.capacity_factor))
    a = s * k

    ids_flat = ids.reshape(b, a)
    gate_flat = gates.reshape(b, a)
    tok_of_a = jnp.broadcast_to(
        jnp.repeat(jnp.arange(s, dtype=jnp.int32), k)[None], (b, a)
    )
    order = jnp.argsort(ids_flat, axis=-1, stable=True)
    sorted_ids = jnp.take_along_axis(ids_flat, order, axis=-1)
    idx = jnp.broadcast_to(jnp.arange(a, dtype=jnp.int32)[None], (b, a))
    is_start = jnp.concatenate(
        [jnp.ones((b, 1), bool), sorted_ids[:, 1:] != sorted_ids[:, :-1]], axis=1
    )
    seg_start = jax.lax.associative_scan(
        jnp.maximum, jnp.where(is_start, idx, 0), axis=1
    )
    pos = idx - seg_start
    keep = pos < capacity
    slot = sorted_ids * capacity + jnp.minimum(pos, capacity - 1)      # (B, A)
    tok_sorted = jnp.take_along_axis(tok_of_a, order, axis=-1)
    gate_sorted = jnp.where(keep, jnp.take_along_axis(gate_flat, order, -1), 0.0)

    x_sorted = jnp.take_along_axis(x, tok_sorted[..., None], axis=1)   # (B, A, D)
    x_sorted = jnp.where(keep[..., None], x_sorted, 0)
    grouped = jnp.zeros((b, e * capacity, d), x.dtype)
    rows = jnp.arange(b, dtype=jnp.int32)[:, None]
    grouped = grouped.at[rows, slot].add(x_sorted)
    grouped = shard(grouped.reshape(b, e, capacity, d), "dp", None, None, None)

    h = _expert_ffn_batched(p["wg"], p["wu"], p["wd"], grouped, act)
    h = h.reshape(b, e * capacity, d)

    y_sorted = jnp.take_along_axis(h, slot[..., None], axis=1) * gate_sorted[..., None]
    y = jnp.zeros_like(x)
    y = y.at[rows, tok_sorted].add(jnp.where(keep[..., None], y_sorted, 0.0))
    return shard(y, "dp", None, None), aux


def _moe_dense_small(p, x, m: MoEConfig, act: str):
    """All experts on all tokens, combined by gate weights (tiny E only)."""
    gates, ids, aux = _router(p, x, m)
    combine = jnp.sum(
        jax.nn.one_hot(ids, m.n_experts, dtype=x.dtype) * gates[..., None], axis=-2
    )  # (..., E)
    dt = x.dtype
    g = jnp.einsum("bsd,edf->besf", x, p["wg"].astype(dt))
    u = jnp.einsum("bsd,edf->besf", x, p["wu"].astype(dt))
    a = jax.nn.silu(g) * u if act == "swiglu" else jax.nn.gelu(g) * u
    h = jnp.einsum("besf,efd->besd", a, p["wd"].astype(dt))
    y = jnp.einsum("besd,bse->bsd", h, combine)
    return y, aux


def _moe_ep_a2a(p, x, m: MoEConfig, act: str, ep_axis):
    """Expert-parallel dispatch: experts sharded over ``ep_axis`` (shard_map).

    Per EP rank: route local tokens, bucket them by destination rank
    (fixed send capacity), all_to_all, run local experts, all_to_all back,
    combine. Two activation-sized collectives instead of per-layer weight
    gathering — the collective-term optimization for the MoE cells.
    """
    axis_size = jax.lax.axis_size(ep_axis)
    e_loc = m.n_experts // axis_size
    b, s, d = x.shape  # local shapes inside shard_map
    gates, ids, aux = _router(p, x, m)
    k = m.top_k
    t = b * s
    x_flat = x.reshape(t, d)
    ids_flat = ids.reshape(t * k)
    gates_flat = gates.reshape(t * k)
    tok_of_a = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)

    # Bucket assignments by destination EP rank, fixed capacity per rank.
    cap_send = max(1, int(t * k / axis_size * m.capacity_factor))
    dest = ids_flat // e_loc
    order, slot, keep = _group_by_expert(dest, axis_size, cap_send)
    send_x = jnp.zeros((axis_size * cap_send, d), x.dtype)
    send_x = send_x.at[slot].add(
        jnp.where(keep[:, None], x_flat[tok_of_a[order]], 0.0)
    )
    send_eid = jnp.full((axis_size * cap_send,), -1, jnp.int32)
    send_eid = send_eid.at[slot].set(
        jnp.where(keep, ids_flat[order] % e_loc, -1)
    )
    # Exchange tokens.
    recv_x = jax.lax.all_to_all(
        send_x.reshape(axis_size, cap_send, d), ep_axis, 0, 0, tiled=False
    ).reshape(axis_size * cap_send, d)
    recv_eid = jax.lax.all_to_all(
        send_eid.reshape(axis_size, cap_send), ep_axis, 0, 0, tiled=False
    ).reshape(axis_size * cap_send)

    # Group received tokens by local expert and run the FFN.
    cap_e = max(1, int(recv_x.shape[0] * m.capacity_factor / e_loc))
    r_order, r_slot, r_keep = _group_by_expert(
        jnp.where(recv_eid >= 0, recv_eid, e_loc), e_loc + 1, cap_e
    )
    grouped = jnp.zeros(((e_loc + 1) * cap_e, d), x.dtype)
    grouped = grouped.at[r_slot].add(
        jnp.where(r_keep[:, None], recv_x[r_order], 0.0)
    )
    h = _expert_ffn(
        p["wg"], p["wu"], p["wd"], grouped.reshape(e_loc + 1, cap_e, d)[:e_loc], act
    )
    h_flat = jnp.concatenate(
        [h.reshape(e_loc * cap_e, d), jnp.zeros((cap_e, d), h.dtype)], axis=0
    )
    y_recv = jnp.zeros_like(recv_x).at[r_order].add(
        jnp.where(r_keep[:, None], h_flat[r_slot], 0.0)
    )
    # Send results home.
    back = jax.lax.all_to_all(
        y_recv.reshape(axis_size, cap_send, d), ep_axis, 0, 0, tiled=False
    ).reshape(axis_size * cap_send, d)
    y_assign = back[slot] * jnp.where(keep, gates_flat[order], 0.0)[:, None]
    y_flat = jnp.zeros_like(x_flat).at[tok_of_a[order]].add(y_assign)
    return y_flat.reshape(b, s, d), aux


def _moe_ep_shard_map(p, x, m: MoEConfig, act: str, ep_axes: tuple):
    """Run the EP dispatch under shard_map: tokens + experts sharded over
    ``ep_axes``; the model ("TP") axis stays GSPMD-automatic.

    Collective profile per layer: 2 activation-sized all_to_alls instead of
    gathering every expert's weights (the §Perf cell-A optimization) — and
    expert-weight gradients become rank-local (no DP all-reduce for them).
    """
    from jax.sharding import PartitionSpec as P

    mesh = jax.sharding.get_abstract_mesh()
    axis_name = ep_axes if len(ep_axes) > 1 else ep_axes[0]
    manual = set(ep_axes)
    auto = frozenset(a for a in mesh.axis_names if a not in manual)

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(
            {
                "router": P(),
                "wg": P(ep_axes, None, None),
                "wu": P(ep_axes, None, None),
                "wd": P(ep_axes, None, None),
            },
            P(ep_axes, None, None),
        ),
        out_specs=(P(ep_axes, None, None), P()),
        axis_names=manual,
    )
    def inner(p_loc, x_loc):
        y, aux = _moe_ep_a2a(p_loc, x_loc, m, act, axis_name)
        return y, jax.lax.pmean(aux, axis_name)

    routed = {k: p[k] for k in ("router", "wg", "wu", "wd")}
    return inner(routed, x)


def moe_apply(
    p: dict,
    x: jax.Array,
    cfg: ModelConfig,
    *,
    ep_axis: Any = None,
):
    """Returns (y, aux_loss). Adds shared experts if configured."""
    m: MoEConfig = cfg.moe
    impl = m.impl
    ep_axes = tuple(ep_axis) if ep_axis else tuple(m.ep_axes)
    if impl == "ep_a2a":
        mesh = jax.sharding.get_abstract_mesh()
        if not ep_axes or mesh.empty or any(a not in mesh.axis_names for a in ep_axes):
            impl = "grouped_local"  # no mesh context (CPU smoke tests)
    if impl == "dense_small":
        y, aux = _moe_dense_small(p, x, m, cfg.act)
    elif impl == "ep_a2a":
        y, aux = _moe_ep_shard_map(p, x, m, cfg.act, ep_axes)
    else:
        y, aux = _moe_grouped_rows(p, x, m, cfg.act)
    if m.n_shared_experts:
        sp = p["shared"]
        dt = x.dtype
        x = shard(x, "dp", None, None)  # pins the bwd cotangent (see layers.mlp)
        g = shard(jnp.einsum("bsd,df->bsf", x, sp["wg"].astype(dt)), "dp", None, "tp")
        u = shard(jnp.einsum("bsd,df->bsf", x, sp["wu"].astype(dt)), "dp", None, "tp")
        a = jax.nn.silu(g) * u if cfg.act == "swiglu" else jax.nn.gelu(g) * u
        y = y + jnp.einsum("bsf,fd->bsd", a, sp["wd"].astype(dt))
    return shard(y, "dp", None, None), aux
