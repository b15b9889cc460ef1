"""Pallas TPU kernel: grouped-query decode attention over a KV cache.

Decode is KV-read bound (its roofline's memory term dominates); this
kernel streams the cache once through VMEM in (block_s) tiles with an online
softmax, computing all G query heads of a KV group against each tile — KV
bytes are read exactly once per group instead of once per query head.

    q     : (B, KV, G, D)    one new token, grouped by KV head
    k, v  : (B, S, KV, D)    cache (storage dtype, e.g. bf16)
    length: (B,)             valid prefix of the cache per sequence
    out   : (B, KV, G, D)

Grid = (B, KV, S/block_s) with the sequence dimension innermost/sequential;
m/l/acc scratch persists across sequence tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_S = 512
NEG_INF = -1e30


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            sm_scale, window, block_s):
    si = pl.program_id(2)
    ns = pl.num_programs(2)

    @pl.when(si == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)          # (G, D)
    k = k_ref[0, :, 0].astype(jnp.float32)       # (block_s, D)
    v = v_ref[0, :, 0].astype(jnp.float32)       # (block_s, D)
    length = len_ref[0]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale
    cols = si * block_s + jax.lax.broadcasted_iota(jnp.int32, (1, block_s), 1)
    mask = cols < length
    if window:
        mask &= cols > length - 1 - window
    s = jnp.where(mask, s, NEG_INF)              # (G, block_s)

    m_prev = m_scr[...]                          # (G, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * alpha + p.sum(axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(si == ns - 1)
    def _finish():
        o_ref[0, 0] = (acc_scr[...] /
                       jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("window", "sm_scale", "block_s", "interpret"))
def decode_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    length: jax.Array,
    *,
    window: int = 0,
    sm_scale: float | None = None,
    block_s: int = DEFAULT_BLOCK_S,
    interpret: bool = False,
) -> jax.Array:
    """See module docstring.  S is zero-padded to a block_s multiple."""
    b, kv, g, d = q.shape
    _, s, kv2, d2 = k.shape
    if kv2 != kv or d2 != d or v.shape != k.shape or length.shape != (b,):
        raise ValueError(f"bad shapes q={q.shape} k={k.shape} len={length.shape}")
    if sm_scale is None:
        sm_scale = d ** -0.5
    bs = min(block_s, s)
    pad = (-s) % bs
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    sp = k.shape[1]
    grid = (b, kv, sp // bs)

    kernel = functools.partial(_kernel, sm_scale=sm_scale, window=window,
                               block_s=bs)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda bb, hh, si: (bb,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, g, d), lambda bb, hh, si: (bb, hh, 0, 0)),
            pl.BlockSpec((1, bs, 1, d), lambda bb, hh, si: (bb, si, hh, 0)),
            pl.BlockSpec((1, bs, 1, d), lambda bb, hh, si: (bb, si, hh, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d), lambda bb, hh, si: (bb, hh, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, kv, g, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ) if not interpret else None,
        interpret=interpret,
    )(length.astype(jnp.int32), q, k, v)
    return out
