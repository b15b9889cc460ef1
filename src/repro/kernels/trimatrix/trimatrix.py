"""Pallas TPU kernel: 2-itemset triangular-matrix counting (paper Phase-2).

Co-occurrence counts over the packed vertical bitmap:

    C[i, j] = sum_w popcount(B[i, w] & B[j, w])

The paper streams the horizontal DB through a Spark accumulator; on TPU the
whole matrix is one blocked popcount-product.  Grid = (N/bn, N/bn, W/bw) with
the W dimension innermost/sequential: each step broadcasts a (bn, bw) row
tile against a (bn, bw) column-row tile, popcounts the (bn, bn, bw) AND, and
accumulates into the (bn, bn) C tile held in VMEM.

Keeping the bitmap packed trades the MXU (which an int8 unpacked `B @ B.T`
would use) for 32x less VMEM traffic per word — the right trade for wide
transaction databases where the product is memory-bound; the unpacked MXU
variant is `ref.cooccurrence_mxu_ref`, checked against the same oracle in
tests/test_kernels.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_N = 128
DEFAULT_BLOCK_W = 128


def _kernel(rows_ref, cols_ref, c_ref):
    w_idx = pl.program_id(2)
    a = rows_ref[...]          # (bn, bw)
    b = cols_ref[...]          # (bn, bw)
    inter = jnp.bitwise_and(a[:, None, :], b[None, :, :])      # (bn, bn, bw)
    partial = jax.lax.population_count(inter).astype(jnp.int32).sum(axis=-1)

    @pl.when(w_idx == 0)
    def _init():
        c_ref[...] = partial

    @pl.when(w_idx != 0)
    def _acc():
        c_ref[...] = c_ref[...] + partial


@functools.partial(jax.jit, static_argnames=("block_n", "block_w", "interpret"))
def trimatrix(
    bitmaps: jax.Array,
    *,
    block_n: int = DEFAULT_BLOCK_N,
    block_w: int = DEFAULT_BLOCK_W,
    interpret: bool = False,
) -> jax.Array:
    """(N, W) uint32 packed bitmap -> (N, N) int32 co-occurrence counts.

    The full square matrix is produced (C is symmetric; the driver reads the
    upper triangle, matching the paper's triangular-matrix storage).
    """
    if bitmaps.ndim != 2:
        raise ValueError(f"expected (N, W), got {bitmaps.shape}")
    n, w = bitmaps.shape
    bn = min(block_n, max(n, 1))
    bw = min(block_w, max(w, 1))
    pad_n = (-n) % bn
    pad_w = (-w) % bw
    x = jnp.pad(bitmaps, ((0, pad_n), (0, pad_w))) if (pad_n or pad_w) else bitmaps
    np_, wp = x.shape
    grid = (np_ // bn, np_ // bn, wp // bw)

    c = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, bw), lambda i, j, k: (i, k)),
            pl.BlockSpec((bn, bw), lambda i, j, k: (j, k)),
        ],
        out_specs=pl.BlockSpec((bn, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((np_, np_), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ) if not interpret else None,
        interpret=interpret,
    )(x, x)
    return c[:n, :n]
