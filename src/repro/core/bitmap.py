"""Packed-bitmap tidsets — the TPU-native vertical data format.

The paper stores a tidset as a variable-length list of transaction ids and
intersects tidsets by merging id lists.  On TPU that access pattern is
hostile (pointer chasing, data-dependent shapes), so the framework adopts the
dense *bitmap* encoding of the vertical database:

    B[i, w] : uint32   bit t%32 of word t//32 set  <=>  item i in txn t

Intersection becomes a bitwise AND over words (VPU) and support counting a
``lax.population_count`` reduction — fixed-shape, fully vectorizable, and the
2-itemset "triangular matrix" of the paper becomes a blocked popcount-matmul
(see ``repro.kernels.trimatrix``).

All helpers here exist in two forms: a NumPy form (host-side encode/compact,
used by the driver the way Spark's driver owns dataset prep) and a jnp form
(device-side inner loop).
"""
from __future__ import annotations

import itertools
from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp

WORD_BITS = 32
_WORD_DTYPE = np.uint32

__all__ = [
    "WORD_BITS",
    "CSR",
    "to_csr",
    "check_csr",
    "n_transactions",
    "n_words",
    "pack_bool_matrix",
    "unpack_bitmap",
    "flatten_transactions",
    "scatter_incidences",
    "incidence_supports",
    "pack_transactions",
    "popcount_np",
    "support_np",
    "support",
    "intersect_support",
    "pair_intersect",
    "bitmap_or_reduce",
    "column_compact",
]


def n_words(n_txn: int) -> int:
    """Number of uint32 words needed for ``n_txn`` transaction columns."""
    return (int(n_txn) + WORD_BITS - 1) // WORD_BITS


def pack_bool_matrix(dense: np.ndarray) -> np.ndarray:
    """Pack a boolean ``(n_items, n_txn)`` matrix into ``(n_items, W)`` uint32.

    Bit layout: transaction ``t`` lives in word ``t // 32`` at bit ``t % 32``.
    """
    dense = np.asarray(dense, dtype=bool)
    if dense.ndim != 2:
        raise ValueError(f"expected 2-D bool matrix, got shape {dense.shape}")
    n_items, n_txn = dense.shape
    w = n_words(n_txn)
    padded = np.zeros((n_items, w * WORD_BITS), dtype=bool)
    padded[:, :n_txn] = dense
    lanes = padded.reshape(n_items, w, WORD_BITS)
    weights = (np.uint64(1) << np.arange(WORD_BITS, dtype=np.uint64)).astype(np.uint64)
    packed = (lanes.astype(np.uint64) * weights).sum(axis=-1)
    return packed.astype(_WORD_DTYPE)


def unpack_bitmap(packed: np.ndarray, n_txn: int) -> np.ndarray:
    """Inverse of :func:`pack_bool_matrix` (host-side; used for compaction)."""
    packed = np.asarray(packed, dtype=_WORD_DTYPE)
    n_items, w = packed.shape
    bits = (packed[:, :, None] >> np.arange(WORD_BITS, dtype=_WORD_DTYPE)) & 1
    dense = bits.reshape(n_items, w * WORD_BITS).astype(bool)
    return dense[:, :n_txn]


class CSR(NamedTuple):
    """A horizontal database as compressed sparse rows: transaction ``t``
    lists ``items[offsets[t]:offsets[t + 1]]``.  The form a packed file is
    read into, and what ``mine`` takes in place of item-id lists when the
    database is too large to hold as Python objects."""

    offsets: np.ndarray
    items: np.ndarray


def to_csr(transactions) -> CSR:
    """Item-id lists -> :class:`CSR` (int64 offsets, int32 items)."""
    txns = transactions if isinstance(transactions, (list, tuple)) else list(transactions)
    lens = np.fromiter(map(len, txns), np.int64, count=len(txns))
    items = np.fromiter(itertools.chain.from_iterable(txns), np.int32,
                        count=int(lens.sum()))
    return CSR(np.concatenate([[0], np.cumsum(lens)]), items)


def n_transactions(transactions) -> int:
    """Number of transactions of item-id lists or of a :class:`CSR`."""
    if isinstance(transactions, CSR):
        return int(np.shape(transactions.offsets)[0]) - 1
    return len(transactions)


def check_csr(csr: CSR, n_items: int):
    """``csr``'s arrays, after checking that they describe a database over
    ``[0, n_items)``: integer 1-D arrays, offsets that start at 0, never
    descend and end at the number of items.  A malformed one raises
    ``ValueError`` naming the first transaction at fault."""
    offsets, items = np.asarray(csr.offsets), np.asarray(csr.items)
    if (offsets.ndim != 1 or items.ndim != 1 or offsets.size == 0
            or offsets.dtype.kind not in "iu" or items.dtype.kind not in "iu"):
        raise ValueError("a CSR database needs 1-D integer offsets (at least "
                         "one) and items")
    if offsets[0] != 0 or offsets[-1] != items.size:
        raise ValueError(f"CSR offsets must run from 0 to the {items.size} "
                         f"items, got {int(offsets[0])}..{int(offsets[-1])}")
    step = np.diff(offsets)
    if step.size and step.min() < 0:
        raise ValueError(f"CSR offsets descend at txn {int(np.argmax(step < 0))}")
    if items.size and (items.min() < 0 or items.max() >= n_items):
        bad = int(np.argmax((items < 0) | (items >= n_items)))
        t = int(np.searchsorted(offsets, bad, side="right")) - 1
        raise ValueError(f"txn {t} has item outside [0, {n_items})")
    return offsets, items


def flatten_transactions(transactions, n_items: int):
    """Flatten a horizontal database (iterable of item-id iterables) into its
    incidence arrays.

    Returns ``(tids, items, n_txn)``: two int64 arrays with one entry per
    listed (transaction, item) incidence, in input order with duplicates
    kept, and the number of transactions.  Lists, tuples, sets and 1-D
    arrays go through one pass: lengths by ``map(len, ...)``, items by
    ``np.fromiter`` over the chained transactions, with no ndarray per
    transaction.  Other iterables (generators, arrays of other ranks) are
    converted one transaction at a time.  An item outside ``[0, n_items)``
    raises ``ValueError`` naming its transaction.  A :class:`CSR` is
    checked (:func:`check_csr`) and expanded.
    """
    if isinstance(transactions, CSR):
        offsets, items = check_csr(transactions, n_items)
        n_txn = offsets.size - 1
        tids = np.repeat(np.arange(n_txn, dtype=np.int64), np.diff(offsets))
        return tids, items.astype(np.int64), n_txn
    txns = transactions if isinstance(transactions, (list, tuple)) else list(transactions)
    n_txn = len(txns)
    try:
        lens = np.fromiter(map(len, txns), np.int64, count=n_txn)
        items = np.fromiter(itertools.chain.from_iterable(txns), np.int64,
                            count=int(lens.sum()))
    except (TypeError, ValueError):
        flat = [np.asarray(t if isinstance(t, (list, tuple, np.ndarray)) else list(t),
                           dtype=np.int64).reshape(-1) for t in txns]
        lens = np.fromiter(map(len, flat), np.int64, count=n_txn)
        items = np.concatenate(flat) if flat else np.zeros(0, np.int64)
    tids = np.repeat(np.arange(n_txn, dtype=np.int64), lens)
    if items.size and (items.min() < 0 or items.max() >= n_items):
        bad = (items < 0) | (items >= n_items)
        t = int(tids[int(np.argmax(bad))])
        raise ValueError(f"txn {t} has item outside [0, {n_items})")
    return tids, items, n_txn


def scatter_incidences(tids: np.ndarray, rows: np.ndarray, n_rows: int,
                       n_txn: int) -> np.ndarray:
    """Packed bitmap ``(n_rows, W)`` with bit ``tids[i]`` of row ``rows[i]``
    set for every ``i``: one vectorised ``np.bitwise_or.at``.  Repeated
    pairs are harmless (OR is idempotent)."""
    packed = np.zeros((n_rows, n_words(n_txn)), dtype=_WORD_DTYPE)
    if tids.size:
        np.bitwise_or.at(
            packed,
            (rows, tids // WORD_BITS),
            _WORD_DTYPE(1) << (tids % WORD_BITS).astype(_WORD_DTYPE),
        )
    return packed


def incidence_supports(tids: np.ndarray, items: np.ndarray, n_items: int,
                       n_txn: int) -> np.ndarray:
    """Exact item supports (int64): the number of distinct transactions
    listing each item.  A ``bincount`` of the items when every transaction
    lists its items in strictly ascending order, which shows no pair is
    repeated; otherwise the popcount of their scatter, which sets a
    repeated pair's bit once."""
    if np.all((items[1:] > items[:-1]) | (tids[1:] > tids[:-1])):
        return np.bincount(items, minlength=n_items).astype(np.int64)
    return support_np(scatter_incidences(tids, items, n_items, n_txn))


def pack_transactions(transactions, n_items: int) -> np.ndarray:
    """Encode a horizontal database (iterable of item-id iterables) into the
    packed vertical bitmap ``(n_items, W)``.

    This is Phase-1's ``flatMapToPair -> groupByKey`` collapsed into a single
    scatter: the database is flattened to one (item, tid) pair list
    (:func:`flatten_transactions`) and every bit is set by one vectorised
    ``np.bitwise_or.at`` (:func:`scatter_incidences`).  Duplicate items
    within a transaction are harmless and out-of-range items are rejected
    with the offending transaction id.
    """
    tids, items, n_txn = flatten_transactions(transactions, n_items)
    return scatter_incidences(tids, items, n_items, n_txn)


def _popcount_swar(x: np.ndarray) -> np.ndarray:
    """SWAR popcount through ``uint64``: the form for NumPy before 2.0."""
    x = np.asarray(x, dtype=np.uint64)
    x = x - ((x >> np.uint64(1)) & np.uint64(0x5555555555555555))
    x = (x & np.uint64(0x3333333333333333)) + ((x >> np.uint64(2)) & np.uint64(0x3333333333333333))
    x = (x + (x >> np.uint64(4))) & np.uint64(0x0F0F0F0F0F0F0F0F)
    return ((x * np.uint64(0x0101010101010101)) >> np.uint64(56)).astype(np.int64)


def _popcount(x) -> np.ndarray:
    """Per-element popcount in a narrow integer dtype: ``np.bitwise_count``
    where NumPy has it (2.0+), else the SWAR form.  Signed input counts the
    bits of its unsigned 64-bit form."""
    x = np.asarray(x)
    if x.dtype.kind != "u":
        x = x.astype(np.uint64)
    if hasattr(np, "bitwise_count"):
        return np.bitwise_count(x)
    return _popcount_swar(x)


def popcount_np(x: np.ndarray) -> np.ndarray:
    """Per-element popcount (int64) for host-side uint32 arrays."""
    return _popcount(x).astype(np.int64)


def support_np(packed: np.ndarray) -> np.ndarray:
    """Host-side row supports (int64) of a packed bitmap ``(n, W)`` -> ``(n,)``."""
    return _popcount(packed).sum(axis=-1, dtype=np.int64)


# ---------------------------------------------------------------------------
# jnp device-side primitives (the executor-task inner loop)
# ---------------------------------------------------------------------------

def support(packed: jax.Array) -> jax.Array:
    """Row supports ``(..., W) -> (...)`` on device."""
    return jax.lax.population_count(packed).astype(jnp.int32).sum(axis=-1)


def intersect_support(a: jax.Array, b: jax.Array):
    """AND two bitmap batches and return (intersection, support).

    The paper's Algorithm-1 lines 8-9:
        tidset(A_ij) = tidset(A_i) ∩ tidset(A_j);  σ = |tidset(A_ij)|
    """
    inter = jnp.bitwise_and(a, b)
    return inter, support(inter)


@jax.jit
def pair_intersect(bitmaps: jax.Array, left: jax.Array, right: jax.Array):
    """Gather rows ``left``/``right`` from ``bitmaps`` and intersect them.

    bitmaps : (P, W) uint32 frontier tidsets
    left/right : (Q,) int32 pair indices (candidate = itemset(left) ∪ item(right))
    returns (Q, W) intersections and (Q,) supports.
    """
    a = jnp.take(bitmaps, left, axis=0)
    b = jnp.take(bitmaps, right, axis=0)
    return intersect_support(a, b)


@jax.jit
def bitmap_or_reduce(packed: jax.Array) -> jax.Array:
    """OR-reduce rows: which transaction columns are touched by any row."""
    return jax.lax.reduce(
        packed, jnp.uint32(0), jax.lax.bitwise_or, dimensions=(0,)
    )


def column_compact(packed: np.ndarray, n_txn: int, keep_cols: np.ndarray):
    """Re-pack a bitmap keeping only ``keep_cols`` transaction columns.

    This is the bitmap form of the paper's filtered-transaction technique
    (EclatV2, Borgelt): after dropping infrequent items, transactions that
    became empty are removed, shrinking the packed width W and hence every
    subsequent AND/popcount.  Host-side (driver) operation.

    The gather works at the word level: output bit ``j`` of each row is read
    directly from word ``keep_idx[j] // 32`` of the source, and the selected
    bits are re-packed with ``np.packbits`` — the only intermediate is one
    byte per *kept* column, never the dense ``(n_items, W*32)`` matrix the
    old path materialized (which blew up memory on wide databases).
    """
    packed = np.asarray(packed, dtype=_WORD_DTYPE)
    keep_cols = np.asarray(keep_cols)
    if keep_cols.dtype == bool:
        keep_idx = np.nonzero(keep_cols[:n_txn])[0]
    else:
        keep_idx = np.asarray(keep_cols, dtype=np.int64)
    n_items = packed.shape[0]
    k = int(keep_idx.shape[0])
    w_out = n_words(k)
    if k == 0:
        return np.zeros((n_items, 0), dtype=_WORD_DTYPE), 0
    src_word = (keep_idx // WORD_BITS).astype(np.int64)
    src_bit = (keep_idx % WORD_BITS).astype(_WORD_DTYPE)
    bits = ((packed[:, src_word] >> src_bit) & _WORD_DTYPE(1)).astype(np.uint8)
    pad = w_out * WORD_BITS - k
    if pad:
        bits = np.pad(bits, ((0, 0), (0, pad)))
    packed_bytes = np.ascontiguousarray(
        np.packbits(bits, axis=-1, bitorder="little"))
    out = packed_bytes.view("<u4").astype(_WORD_DTYPE)
    return out, k
