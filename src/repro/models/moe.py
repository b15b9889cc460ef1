"""Mixture-of-Experts with sort-based dispatch and Eclat-style placement.

Two dispatch strategies (``cfg.moe_dispatch``):

local (default, production)
    Tokens are grouped by data shard; top-k routing, the expert-id sort and
    the capacity scatter are all *shard-local* (batched over the group axis,
    so GSPMD keeps them collective-free).  The capacity buffer is then
    constrained from group-sharded to expert-sharded — exactly one
    all-to-all — batch-GEMMed against the stacked expert weights (d_ff
    tensor-parallel over 'model'), constrained back, and combined locally.

global (recorded baseline, §Perf)
    One flat argsort over every routed token; GSPMD turns the global sort
    into a distributed sort — the measured collective catastrophe the §Perf
    log starts from (llama4 train: 98.7 s collective term).

Expert -> device placement reuses the paper's equivalence-class partitioners
(``repro.core.partitioners``): balancing routed load over EP shards is the
same irregular-work-unit assignment the paper solves for equivalence
classes; ``expert_placement="greedy"`` permutes expert ids so heavy experts
spread across the EP axis.  Capacity overflow drops
tokens (weight 0) — the padding-efficiency knob the paper's balance metric
measures.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..dist.sharding import constrain, dp_axes, get_mesh


def init_moe(key, cfg, dtype, stacked: int = 0) -> dict:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    split = getattr(cfg, "expert_split", 1)
    slots, fs = e * split, f // split   # expert fission (see module docstring)
    ks = jax.random.split(key, 4)
    shp = (lambda *s: (stacked, *s)) if stacked else (lambda *s: s)
    pre = "stk_" if stacked else ""
    p = {
        pre + "router": jax.random.normal(ks[0], shp(d, e), jnp.float32) * d ** -0.5,
        pre + "experts_up": jax.random.normal(ks[2], shp(slots, d, fs), dtype) * d ** -0.5,
        pre + "experts_down": jax.random.normal(ks[3], shp(slots, fs, d), dtype) * f ** -0.5,
    }
    if cfg.mlp_act in ("swiglu", "geglu"):
        p[pre + "experts_gate"] = jax.random.normal(ks[1], shp(slots, d, fs), dtype) * d ** -0.5
    return p


def expert_placement(cfg, load_estimate: Optional[np.ndarray] = None) -> np.ndarray:
    """Static expert-id permutation balancing load across the EP axis
    (greedy-LPT from repro.core.partitioners; see module docstring)."""
    e = cfg.n_experts
    if cfg.expert_placement == "default" or e == 0:
        return np.arange(e, dtype=np.int32)
    from ..core.partitioners import greedy_partitioner

    load = load_estimate if load_estimate is not None else np.ones(e)
    shards = 16 if e % 16 == 0 else max(1, e // 8)
    assign = greedy_partitioner(np.arange(e), shards, work=np.asarray(load, np.float64))
    perm = np.argsort(assign, kind="stable").astype(np.int32)
    return perm


def _n_groups(cfg, tokens: int) -> int:
    mesh = get_mesh()
    if mesh is None:
        return 1
    g = 1
    for a in dp_axes(mesh):
        g *= mesh.shape[a]
    while g > 1 and tokens % g:
        g //= 2
    return max(g, 1)


def _dispatch_one_group(xf, probs, k, e, cap, placement, split: int = 1):
    """Single group (no leading axis): returns buffers + combine metadata."""
    tg, d = xf.shape
    gate_vals, expert_ids = jax.lax.top_k(probs, k)                # (Tg, k)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)
    if placement is not None:
        expert_ids = placement[expert_ids]
    if split > 1:
        # expert fission: expert -> its `split` slots, same gate weight each
        expert_ids = (expert_ids[..., None] * split +
                      jnp.arange(split)).reshape(tg, k * split)
        gate_vals = jnp.repeat(gate_vals, split, axis=-1)
        k = k * split
    n_slots = e * split
    flat_e = expert_ids.reshape(tg * k)
    flat_g = gate_vals.reshape(tg * k)
    flat_t = jnp.repeat(jnp.arange(tg), k)
    order = jnp.argsort(flat_e)                                     # local sort
    se, st, sg = flat_e[order], flat_t[order], flat_g[order]
    pos = jnp.arange(tg * k)
    seg_start = jnp.full((n_slots,), tg * k, jnp.int32).at[se].min(
        pos.astype(jnp.int32), mode="drop")
    pos_in_e = pos.astype(jnp.int32) - seg_start[se]
    keep = pos_in_e < cap
    dest = se * cap + jnp.minimum(pos_in_e, cap - 1)
    buf = jnp.zeros((n_slots * cap, d), xf.dtype).at[dest].add(
        jnp.where(keep[:, None], xf[st], 0), mode="drop")
    return buf.reshape(n_slots, cap, d), (dest, st, sg, keep)


def _combine_one_group(out_buf, meta, tg, d):
    dest, st, sg, keep = meta
    gathered = out_buf.reshape(-1, out_buf.shape[-1])[dest]
    weighted = gathered.astype(jnp.float32) * jnp.where(keep, sg, 0.0)[:, None]
    return jnp.zeros((tg, d), jnp.float32).at[st].add(weighted, mode="drop")


def moe(p: dict, x: jax.Array, cfg, placement: Optional[jax.Array] = None):
    """x: (B, S, D) -> (B, S, D), plus aux dict (load stats, router loss)."""
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    t = b * s
    dp = dp_axes()
    e_ax = dp[-1] if cfg.expert_sharding in ("ep", "ep_pad") else None
    f_ax = "model" if cfg.expert_sharding in ("ep", "ep_pad") else tuple(list(dp) + ["model"])

    xf = x.reshape(t, d)
    logits = xf.astype(jnp.float32) @ p["router"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                        # (T, E)

    # Switch-style load-balance loss + stats (global)
    me = probs.mean(0)
    top1 = jnp.argmax(probs, axis=-1)
    ce = jnp.zeros((e,), jnp.float32).at[top1].add(1.0) / t
    aux_loss = e * jnp.sum(me * ce)

    # expert fission: token -> all `split` half-d_ff slots of its expert;
    # slot outputs sum in the combine (exact: gated FFNs are elementwise in f)
    split = getattr(cfg, "expert_split", 1)
    slots = e * split

    if cfg.moe_dispatch == "global":
        out, dropped = _moe_global(p, xf, probs, cfg, placement, e_ax, f_ax)
    else:
        g = _n_groups(cfg, t)
        tg = t // g
        cap = int(np.ceil(tg * k / e * cfg.capacity_factor))
        xg = constrain(xf.reshape(g, tg, d), P(dp, None, None))
        pg = probs.reshape(g, tg, e)
        bufs, meta = jax.vmap(
            lambda xx, pp: _dispatch_one_group(xx, pp, k, e, cap, placement,
                                               split=split)
        )(xg, pg)                                                   # (G, slots, C, D)
        # ONE all-to-all: group-sharded -> expert-sharded
        bufs = constrain(bufs, P(None, e_ax, None, None))
        up = jnp.einsum("gecd,edf->gecf", bufs, p["experts_up"])
        up = constrain(up, P(None, e_ax, None, "model" if f_ax == "model" else None))
        if cfg.mlp_act in ("swiglu", "geglu"):
            gate = jnp.einsum("gecd,edf->gecf", bufs, p["experts_gate"])
            act = jax.nn.silu(gate) if cfg.mlp_act == "swiglu" else \
                jax.nn.gelu(gate, approximate=True)
            hidden = act * up
        else:
            hidden = jax.nn.gelu(up, approximate=True)
        out_buf = jnp.einsum("gecf,efd->gecd", hidden, p["experts_down"])
        # all-to-all back: expert-sharded -> group-sharded
        out_buf = constrain(out_buf, P(dp, None, None, None))
        out = jax.vmap(
            lambda ob, de, st_, sg_, kp: _combine_one_group(ob, (de, st_, sg_, kp), tg, d)
        )(out_buf, *meta)
        out = out.reshape(t, d)
        dropped = 1.0 - jnp.mean(meta[3].astype(jnp.float32))

    out = constrain(out.reshape(b, s, d).astype(x.dtype), P(dp, None, None))
    aux = {"aux_loss": aux_loss, "expert_load": ce, "dropped_frac": dropped}
    return out, aux


def _moe_global(p, xf, probs, cfg, placement, e_ax, f_ax):
    """Naive flat dispatch (the §Perf baseline): one global argsort."""
    t, d = xf.shape
    e, k = cfg.n_experts, cfg.top_k
    gate_vals, expert_ids = jax.lax.top_k(probs, k)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)
    if placement is not None:
        expert_ids = placement[expert_ids]
    flat_e = expert_ids.reshape(t * k)
    flat_g = gate_vals.reshape(t * k)
    flat_t = jnp.repeat(jnp.arange(t), k)
    cap = int(np.ceil(t * k / e * cfg.capacity_factor))
    order = jnp.argsort(flat_e)
    se, st, sg = flat_e[order], flat_t[order], flat_g[order]
    pos = jnp.arange(t * k, dtype=jnp.int32)
    seg_start = jnp.full((e,), t * k, jnp.int32).at[se].min(pos, mode="drop")
    pos_in_e = pos - seg_start[se]
    keep = pos_in_e < cap
    dest = se * cap + jnp.minimum(pos_in_e, cap - 1)
    buf = jnp.zeros((e * cap, d), xf.dtype).at[dest].add(
        jnp.where(keep[:, None], xf[st], 0).astype(xf.dtype), mode="drop")
    buf = buf.reshape(e, cap, d)
    buf = constrain(buf, P(e_ax, None, None))
    up = jnp.einsum("ecd,edf->ecf", buf, p["experts_up"])
    if cfg.mlp_act in ("swiglu", "geglu"):
        gate = jnp.einsum("ecd,edf->ecf", buf, p["experts_gate"])
        act = jax.nn.silu(gate) if cfg.mlp_act == "swiglu" else \
            jax.nn.gelu(gate, approximate=True)
        hidden = act * up
    else:
        hidden = jax.nn.gelu(up, approximate=True)
    out_buf = jnp.einsum("ecf,efd->ecd", hidden, p["experts_down"]).reshape(e * cap, d)
    gathered = out_buf[dest]
    weighted = gathered.astype(jnp.float32) * jnp.where(keep, sg, 0.0)[:, None]
    out = jnp.zeros((t, d), jnp.float32).at[st].add(weighted)
    return out, 1.0 - jnp.mean(keep.astype(jnp.float32))
