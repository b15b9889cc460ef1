"""Triangular-matrix 2-itemset counting (paper Phase-2).

The paper updates a shared upper-triangular ``long[]`` through a Spark
accumulator while streaming the horizontal DB.  With packed bitmaps the whole
matrix is a popcount co-occurrence product

    C[i, j] = sum_w popcount(B[i, w] & B[j, w])

which is the ``repro.kernels.trimatrix`` Pallas kernel on TPU.  On the CPU
host (this container) we use the blocked jnp path below; ``repro.kernels``
tests assert the kernel matches it bit-exactly in interpret mode.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from functools import partial

__all__ = ["cooc_blocks", "cooccurrence_counts", "frequent_pairs"]


@partial(jax.jit, static_argnames=("block",))
def _cooc_block(bitmaps: jax.Array, block: int) -> jax.Array:
    """Counts of every row against every row, ``block`` rows at a time, in
    one device program; the row count of ``bitmaps`` is a multiple of
    ``block``."""
    n_pad, words = bitmaps.shape

    def count(rows):
        inter = jnp.bitwise_and(rows[:, None, :], bitmaps[None, :, :])
        return jax.lax.population_count(inter).astype(jnp.int32).sum(-1)
    with jax.named_scope("level2_cooc"):
        per_block = jax.lax.map(
            count, bitmaps.reshape(n_pad // block, block, words))
        return per_block.reshape(n_pad, n_pad)


@partial(jax.jit, static_argnames=("pad",))
def _pad_rows(bitmaps: jax.Array, pad: int) -> jax.Array:
    # the fill constant is baked in at trace time — a bare jnp.pad at the
    # call site would dispatch it as an implicit host scalar, tripping the
    # steady-state transfer guard (staticcheck SH002)
    return jnp.pad(bitmaps, ((0, pad), (0, 0)))


def _padded_rows(n: int, block: int) -> int:
    """``n`` padded to ``block`` times a power of two, so nearby ``n``
    reuse one compiled :func:`_cooc_block`."""
    blocks = 1
    while blocks * block < n:
        blocks <<= 1
    return blocks * block


def cooc_blocks(n: int) -> int:
    """Blocking device->host reads one :func:`cooccurrence_counts` pass
    over ``n`` rows makes: one, or none when there are no rows."""
    return 1 if n else 0


def cooccurrence_counts(bitmaps, block: int = 64) -> np.ndarray:
    """Full (n, n) co-occurrence count matrix: one device program over
    ``block``-row blocks, so the (block, n, W) intermediate stays
    cache/VMEM sized, and one blocking read."""
    if not isinstance(bitmaps, jax.Array):
        # explicit upload (staticcheck RS005): callers on the slide hot path
        # hand device arrays in; host arrays are device_put once, up front
        bitmaps = jax.device_put(np.ascontiguousarray(bitmaps))
    n = bitmaps.shape[0]
    if n == 0:
        return np.zeros((0, 0), np.int32)
    pad = _padded_rows(n, block) - n
    bitmaps_p = _pad_rows(bitmaps, pad) if pad else bitmaps
    return jax.device_get(_cooc_block(bitmaps_p, block))[:n, :n]


def frequent_pairs(counts: np.ndarray, min_sup: int):
    """Upper-triangular (i < j) index pairs with count >= min_sup."""
    n = counts.shape[0]
    iu, ju = np.triu_indices(n, k=1)
    keep = counts[iu, ju] >= int(min_sup)
    return iu[keep].astype(np.int64), ju[keep].astype(np.int64), counts[iu, ju][keep]
