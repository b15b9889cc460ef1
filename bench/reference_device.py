"""The plain frequent-itemset miner of ``reference.py``, counted on the device.

The same answer as :func:`bench.reference.mine` -- items in ascending id
order, candidates formed within classes by ``reference``'s own class pairs,
supports exact, the threshold ``ceil(min_sup * n_txn)`` -- for a database
whose host reference would take minutes.  Only the supports are computed
elsewhere: a candidate's support is the popcount of the AND of its items'
tidset rows, in plain ``jax.numpy`` over blocks of candidates on one device.
No frontier of intersections is kept, so the device holds only the
``(n_items, ceil(n_txn / 32))`` item rows.  It imports nothing of the
program under test.
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from .generators import Database
from .reference import _class_pairs, abs_min_sup

__all__ = ["bitmaps", "mine"]

BLOCK_TXNS = 1 << 20      # transactions scattered per device call
BLOCK_ROWS = 1024         # candidates counted per device call


@functools.partial(jax.jit, static_argnames=("n_items", "n_txn"))
def _pack_block(txn, item, *, n_items: int, n_txn: int):
    """``(n_items, n_txn // 32)`` uint32 rows of one block of incidences
    (``txn`` local to the block; pairs past it carry ``n_items``).  A
    database's pairs are distinct, so adding each bit into its word sets
    it."""
    bit = jnp.left_shift(jnp.uint32(1), (txn % 32).astype(jnp.uint32))
    return jnp.zeros((n_items, n_txn // 32), jnp.uint32).at[
        item, txn // 32].add(bit, mode="drop")


def bitmaps(db: Database) -> jax.Array:
    """``(n_items, ceil(n_txn / 32))`` uint32 tidset rows on the device, bit
    ``t % 32`` of word ``t // 32`` set where transaction ``t`` holds the item."""
    block = min(BLOCK_TXNS, 32 * max(-(-db.n_txn // 32), 1))
    cuts = np.searchsorted(db.txn, np.arange(0, db.n_txn + block, block))
    width = max(int(np.diff(cuts).max()), 1)
    parts = []
    for b in range(cuts.size - 1):
        txn = np.zeros(width, np.int32)
        item = np.full(width, db.n_items, np.int32)
        lo, hi = cuts[b], cuts[b + 1]
        txn[:hi - lo] = db.txn[lo:hi] - b * block
        item[:hi - lo] = db.item[lo:hi]
        parts.append(_pack_block(jax.device_put(txn), jax.device_put(item),
                                 n_items=db.n_items, n_txn=block))
    rows = jnp.concatenate(parts, axis=1) if len(parts) > 1 else parts[0]
    return rows[:, :-(-db.n_txn // 32)]


@jax.jit
def _supports(rows, idx):
    """Popcount of the AND of the rows ``idx[c, :]``, for each candidate
    ``c``; a candidate's repeated item ANDs its row again, which is
    idempotent."""
    acc = jax.lax.fori_loop(
        1, idx.shape[1],
        lambda j, a: a & jnp.take(rows, idx[:, j], axis=0),
        jnp.take(rows, idx[:, 0], axis=0))
    return jax.lax.population_count(acc).sum(axis=1, dtype=jnp.int32)


def _count(rows: jax.Array, sets: np.ndarray) -> np.ndarray:
    """Supports of the itemsets ``sets`` (one row of item ids each), in
    blocks of ``BLOCK_ROWS``; each block is padded to a power-of-two width
    with its last item and to ``BLOCK_ROWS`` rows with item 0."""
    m, k = sets.shape
    width = 1 << (k - 1).bit_length()
    out = []
    for s in range(0, m, BLOCK_ROWS):
        part = sets[s:s + BLOCK_ROWS]
        idx = np.zeros((BLOCK_ROWS, width), np.int32)
        idx[:part.shape[0], :k] = part
        idx[:part.shape[0], k:] = part[:, -1:]
        out.append(_supports(rows, jax.device_put(idx)))
    return np.concatenate(jax.device_get(out))[:m].astype(np.int64)


def mine(db: Database, min_sup) -> Dict[Tuple[int, ...], int]:
    """Every itemset with support >= ``abs_min_sup(min_sup, db.n_txn)``,
    as ``{sorted item-id tuple: support}``: ``reference.mine``'s answer."""
    thr = abs_min_sup(min_sup, db.n_txn)
    item_sup = db.supports()
    freq = np.nonzero(item_sup >= thr)[0]
    answer = {(int(i),): int(item_sup[i]) for i in freq}
    sets = freq[:, None]
    rows = bitmaps(db)
    while sets.shape[0] > 1:
        left, right = _class_pairs(sets)
        if left.size == 0:
            break
        cand = np.concatenate([sets[left], sets[right][:, -1:]], axis=1)
        sup = _count(rows, cand)
        keep = sup >= thr
        sets = cand[keep]
        answer.update(zip(map(tuple, sets.tolist()), sup[keep].tolist()))
    return answer
