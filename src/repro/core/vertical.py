"""Vertical database construction (Phase-1/2/3 of the paper's variants).

One builder, :func:`build_vertical`, covers the paper's three phases on the
flat (transaction, item) incidence arrays of the horizontal database:

* EclatV1 Phase-1: scatter every incidence into a packed bitmap, compute
  item supports, keep frequent items.
* EclatV2 Phase-2 (``filter_txns``): Borgelt's filtered transactions, applied
  before the pack -- incidences of infrequent items are dropped and the
  transactions left with none are removed, so the packed width W shrinks.
* EclatV3 Phase-3 (``mesh``): the accumulator-built vertical DB.  Each shard
  of the data axis scatters its own block of transactions into a partial
  bitmap, and the bit-disjoint partials are merged by ``psum`` (add == or).

:func:`filter_transactions` is the same filter for a caller that already
holds a :class:`VerticalDB`: a bitmap column compaction.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..dist.compat import shard_map
from . import bitmap as bm

__all__ = ["VerticalDB", "build_vertical", "filter_transactions", "sort_items"]


@dataclasses.dataclass
class VerticalDB:
    """Frequent-item vertical database.

    Attributes:
      bitmaps:   (n_freq, W) uint32 packed tidsets, row order == ``items`` order.
      items:     (n_freq,) original item ids for each row.
      supports:  (n_freq,) int64 item supports.
      n_txn:     number of (possibly compacted) transaction columns.
      order:     how ``items`` rows are sorted ("support_asc" | "lex").
      n_incidences: distinct (transaction, item) bits the build scattered,
                 infrequent items included.
    """

    bitmaps: np.ndarray
    items: np.ndarray
    supports: np.ndarray
    n_txn: int
    order: str = "support_asc"
    n_incidences: int = 0

    @property
    def n_items(self) -> int:
        return int(self.items.shape[0])

    @property
    def n_words(self) -> int:
        return int(self.bitmaps.shape[1])

    def validate(self) -> None:
        # a real integrity check, not an ``assert`` — it must also hold
        # under ``python -O`` (staticcheck RS001)
        want = (self.items.shape[0], bm.n_words(self.n_txn))
        if self.bitmaps.shape != want:
            raise RuntimeError(
                f"vertical bitmap shape drifted: expected {want}, got "
                f"{self.bitmaps.shape}")
        np.testing.assert_array_equal(bm.support_np(self.bitmaps), self.supports)


def sort_items(items: np.ndarray, supports: np.ndarray, order: str):
    """Total order used for equivalence-class construction.

    ``support_asc`` (paper: "sorted ... by the total order of increasing
    support count") breaks ties lexicographically so the order is
    deterministic.  ``lex`` is the alphanumeric order of EclatV2 Phase-1.
    """
    if order == "support_asc":
        perm = np.lexsort((items, supports))
    elif order == "lex":
        perm = np.argsort(items, kind="stable")
    else:
        raise ValueError(f"unknown item order {order!r}")
    return perm


def _psum_pack(tids: np.ndarray, rows: np.ndarray, n_rows: int, n_txn: int,
               mesh: jax.sharding.Mesh, axis: str) -> np.ndarray:
    """The accumulator merge: shard ``i`` of ``mesh``'s ``axis`` scatters the
    ``i``-th contiguous block of transaction ids into its own full-width
    partial, and ``psum`` adds the bit-disjoint partials, which is their OR."""
    d = mesh.shape[axis]
    bounds = np.linspace(0, n_txn, d + 1).astype(int)
    cuts = np.searchsorted(tids, bounds)            # tids ascend
    partials = np.stack([
        bm.scatter_incidences(tids[cuts[i]:cuts[i + 1]], rows[cuts[i]:cuts[i + 1]],
                              n_rows, n_txn)
        for i in range(d)
    ])

    def _merge(part):  # part: (1, n_rows, w) per shard
        return jax.lax.psum(part[0], axis)

    merged = jax.jit(
        shard_map(_merge, mesh=mesh, in_specs=P(axis, None, None), out_specs=P())
    )(jnp.asarray(partials))
    return np.asarray(merged).astype(np.uint32)


def build_vertical(
    transactions: Sequence[Sequence[int]],
    n_items: int,
    min_sup: int,
    order: str = "support_asc",
    *,
    filter_txns: bool = False,
    mesh: Optional[jax.sharding.Mesh] = None,
    axis: str = "data",
) -> VerticalDB:
    """Horizontal -> packed vertical DB of frequent items.

    The input is flattened once into incidence arrays; item supports are
    counted on them exactly (:func:`bm.incidence_supports`), and the
    incidences of frequent items are packed into their rows by one
    vectorised scatter -- on the host, or with ``mesh`` per shard of
    ``axis`` with a ``psum`` merge.

    ``filter_txns`` drops the transactions left with no frequent item
    before the pack and renumbers the rest in their original order: the
    same bitmaps as :func:`filter_transactions` on the unfiltered DB, with
    no bit-level column gather.  ``n_txn`` is then the number of
    transactions kept; supports and ``n_incidences`` are those of the whole
    input.
    """
    tids, items, n_txn = bm.flatten_transactions(transactions, n_items)
    supports = bm.incidence_supports(tids, items, n_items, n_txn)
    n_incidences = int(supports.sum())
    freq_mask = supports >= int(min_sup)
    freq_items = np.nonzero(freq_mask)[0].astype(np.int64)
    row_of = np.where(freq_mask, np.cumsum(freq_mask) - 1, -1)
    rows = row_of[items]
    keep = rows >= 0
    tids, rows = tids[keep], rows[keep]
    if filter_txns:
        touched = np.zeros(n_txn, dtype=bool)
        touched[tids] = True
        n_kept = int(np.count_nonzero(touched))
        if n_kept < n_txn:
            tids = (np.cumsum(touched) - 1)[tids]
            n_txn = n_kept
    if mesh is None:
        packed = bm.scatter_incidences(tids, rows, freq_items.size, n_txn)
    else:
        packed = _psum_pack(tids, rows, freq_items.size, n_txn, mesh, axis)
    supports = supports[freq_mask]
    perm = sort_items(freq_items, supports, order)
    return VerticalDB(
        bitmaps=packed[perm],
        items=freq_items[perm],
        supports=supports[perm],
        n_txn=n_txn,
        order=order,
        n_incidences=n_incidences,
    )


def filter_transactions(db: VerticalDB, drop_empty_cols: bool = True) -> VerticalDB:
    """EclatV2's filtered-transaction technique as bitmap compaction.

    The infrequent item *rows* are already gone after ``build_vertical``; the
    remaining saving — exactly the paper's observation that filtering only
    pays when the DB shrinks "significantly" — is removing transaction
    columns containing no frequent item, which shrinks W for every later AND.
    """
    if not drop_empty_cols:
        return db
    # word-level column occupancy: OR-reduce the rows, then test each
    # transaction's bit — no dense (n_items, n_txn) matrix is materialized
    orred = np.bitwise_or.reduce(db.bitmaps, axis=0) if db.n_items else np.zeros(
        bm.n_words(db.n_txn), db.bitmaps.dtype)
    t = np.arange(db.n_txn)
    touched = ((orred[t // bm.WORD_BITS] >> (t % bm.WORD_BITS).astype(orred.dtype)) & 1).astype(bool)
    if touched.all():
        return db  # nothing to compact; avoid a useless repack
    compact, kept = bm.column_compact(db.bitmaps, db.n_txn, touched)
    return VerticalDB(
        bitmaps=compact,
        items=db.items,
        supports=db.supports,
        n_txn=kept,
        order=db.order,
        n_incidences=db.n_incidences,
    )
