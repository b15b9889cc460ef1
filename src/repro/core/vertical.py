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
  This is the build for a replicated frontier (the pair-sharded engine).

:func:`build_vertical_sharded` is the build for a word-sharded frontier
(DESIGN.md §7): each device receives its own transactions' CSR slice and
scatters them into its own word slice on the device; item supports merge
by one ``psum`` of the per-shard counts (the paper's accumulator) and the
frequent rows are ranked and gathered in place.  No full-width bitmap
exists on the host, on one device or on the interconnect.

:func:`filter_transactions` is the same filter for a caller that already
holds a :class:`VerticalDB`: a bitmap column compaction.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence, Union

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..dist.compat import shard_map
from . import bitmap as bm

__all__ = ["VerticalDB", "build_vertical", "build_vertical_sharded",
           "filter_transactions", "sort_items"]


@dataclasses.dataclass
class VerticalDB:
    """Frequent-item vertical database.

    Attributes:
      bitmaps:   (n_freq, W) uint32 packed tidsets, row order == ``items`` order:
                 a host array, or for :func:`build_vertical_sharded` a device
                 array placed ``P(None, axis)`` whose shards each end in zero
                 padding words.
      items:     (n_freq,) original item ids for each row.
      supports:  (n_freq,) int64 item supports.
      n_txn:     number of (possibly compacted) transaction columns.
      order:     how ``items`` rows are sorted ("support_asc" | "lex").
      n_incidences: distinct (transaction, item) bits the build scattered,
                 infrequent items included.
      host_bitmap_bytes: bitmap bytes the build moved through the host
                 (built there, or merged partials uploaded and read back).
      psum_calls: collectives the build issued.
    """

    bitmaps: Union[np.ndarray, jax.Array]
    items: np.ndarray
    supports: np.ndarray
    n_txn: int
    order: str = "support_asc"
    n_incidences: int = 0
    host_bitmap_bytes: int = 0
    psum_calls: int = 0

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

    merged = _merge_program(mesh, axis)(jnp.asarray(partials))
    return np.asarray(merged).astype(np.uint32)


@functools.lru_cache(maxsize=None)
def _merge_program(mesh, axis: str):
    """``psum`` of per-shard ``(1, n_rows, w)`` partials, built once per mesh."""

    def _merge(part):
        return jax.lax.psum(part[0], axis)

    return jax.jit(shard_map(_merge, mesh=mesh, in_specs=P(axis, None, None),
                             out_specs=P()))


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
        moved = packed.nbytes
    else:
        packed = _psum_pack(tids, rows, freq_items.size, n_txn, mesh, axis)
        # the partials go up, the merge comes back, the ranked rows go up
        moved = (mesh.shape[axis] + 2) * packed.nbytes
    supports = supports[freq_mask]
    perm = sort_items(freq_items, supports, order)
    return VerticalDB(
        bitmaps=packed[perm],
        items=freq_items[perm],
        supports=supports[perm],
        n_txn=n_txn,
        order=order,
        n_incidences=n_incidences,
        host_bitmap_bytes=moved,
        psum_calls=int(mesh is not None),
    )


# ---------------------------------------------------------------------------
# per-shard device build (word-sharded frontier)
# ---------------------------------------------------------------------------

# A shard's word count is a multiple of this tile (or, below it, a power of
# two), so that every tile width the fused kernel may pick divides the
# frontier and no call pads (copies) it.
_SHARD_WORD_TILE = 2048


def _shard_words(n_txn: int) -> int:
    """Words one shard holds for ``n_txn`` transactions, rounded up to the
    tile that keeps the kernel from padding the frontier."""
    w = max(bm.n_words(n_txn), 1)
    tile = min(_SHARD_WORD_TILE, 1 << (w - 1).bit_length())
    return -(-w // tile) * tile


def _stable_size(total: int, shards: int, widest: int, granule: int) -> int:
    """A per-shard length that holds ``widest``: the shards' mean plus 1/64,
    rounded up to ``granule``, or ``widest`` rounded up where it is larger.
    ``total`` does not change when the transactions are put in another
    order, so neither do the shapes (and the compiled programs) of such an
    input, unless one shard holds more than 1/64 above the mean."""
    mean = -(-int(total) * 65 // (shards * 64))
    return -(-max(mean, int(widest)) // granule) * granule


def _shard_slices(offsets: np.ndarray, items: np.ndarray, shards: int,
                  t_shard: int):
    """Each shard's CSR slice, its offsets made local: ``(shards, t_shard +
    1)`` int32 offsets and ``(shards, L)`` int32 items, ``L`` a stable size
    (:func:`_stable_size`); the scatter masks a slice's items beyond its
    own end."""
    n_txn = offsets.size - 1
    starts = np.minimum(np.arange(shards + 1) * t_shard, n_txn)
    cuts = offsets[starts]
    widest = int(np.diff(cuts).max())
    length = max(_stable_size(items.size, shards, widest, 1024), 1)
    if length >= 2**31:
        raise ValueError(f"a shard of {length} incidences overflows int32")
    local = np.zeros((shards, t_shard + 1), np.int32)
    flat = np.zeros((shards, length), np.int32)
    for j in range(shards):
        o = offsets[starts[j]:starts[j + 1] + 1] - cuts[j]
        local[j, :o.size] = o
        local[j, o.size:] = o[-1]
        flat[j, :o[-1]] = items[cuts[j]:cuts[j + 1]]
    return local, flat


@functools.lru_cache(maxsize=None)
def _scatter_program(mesh, axis: str, n_items: int):
    """Per shard: scatter the CSR slice into a dense ``(n_items, 32,
    t_shard // 32)`` uint8 membership block (transaction ``t`` at ``[:, t %
    32, t // 32]``), count its item supports, ``psum`` them, and count the
    transactions holding a frequent item."""

    def shard_scatter(offs, items, min_sup):
        offs, items = offs[0], items[0]
        nnz = items.shape[0]
        words = (offs.shape[0] - 1) // bm.WORD_BITS
        pos = jnp.arange(nnz, dtype=jnp.int32)
        # incidence -> local transaction: one mark where each transaction
        # after the first starts, summed
        txn = jnp.cumsum(jnp.zeros(nnz, jnp.int32).at[offs[1:-1]].add(
            1, mode="drop"))
        row = jnp.where(pos < offs[-1], items, n_items)   # past the slice
        dense = jnp.zeros((n_items, bm.WORD_BITS, words), jnp.uint8).at[
            row, txn % bm.WORD_BITS, txn // bm.WORD_BITS].set(1, mode="drop")
        sup = jax.lax.psum(dense.sum(axis=(1, 2), dtype=jnp.int32), axis)
        freq = (sup >= min_sup).astype(jnp.uint8)
        kept = jnp.max(dense * freq[:, None, None], axis=0, initial=0).sum(
            dtype=jnp.int32)
        return dense, sup, kept[None]

    return jax.jit(shard_map(
        shard_scatter, mesh=mesh,
        in_specs=(P(axis, None), P(axis, None), P()),
        out_specs=(P(None, None, axis), P(), P(axis))))


@functools.lru_cache(maxsize=None)
def _pack_program(mesh, axis: str, words_out: int, filter_txns: bool):
    """Per shard: gather the ranked frequent rows of the membership block,
    drop (v2+ filter) the transactions left with none, and pack the rest
    into ``words_out`` words (the shard's own, without the filter), zero
    words past the transactions it kept."""

    def shard_pack(dense, rows):
        block = jnp.take(dense, rows, axis=0)          # (n_freq, 32, W)
        if filter_txns:
            t_in = bm.WORD_BITS * block.shape[2]
            held = jnp.max(block, axis=0, initial=0) > 0   # (32, W)
            cols = jnp.nonzero(held.T.reshape(t_in),
                               size=bm.WORD_BITS * words_out,
                               fill_value=t_in)[0]
            cols = cols.reshape(words_out, bm.WORD_BITS).T       # [b, w]
            block = block.at[:, cols % bm.WORD_BITS,
                             cols // bm.WORD_BITS].get(mode="fill",
                                                       fill_value=0)
        # bit b of word w from block[:, b, w]: a sum over the bit axis keeps
        # the long word axis the lane axis on a TPU
        shift = jnp.arange(bm.WORD_BITS, dtype=jnp.uint32)[:, None]
        return jnp.sum(block.astype(jnp.uint32) << shift, axis=1,
                       dtype=jnp.uint32)

    return jax.jit(shard_map(shard_pack, mesh=mesh,
                             in_specs=(P(None, None, axis), P()),
                             out_specs=P(None, axis)))


def build_vertical_sharded(
    transactions,
    n_items: int,
    min_sup: int,
    order: str = "support_asc",
    *,
    filter_txns: bool = False,
    mesh: jax.sharding.Mesh,
    axis: str = "data",
) -> VerticalDB:
    """Horizontal -> packed vertical DB of frequent items, built per shard of
    ``mesh``'s ``axis`` and born placed ``P(None, axis)``.

    Shard ``j`` receives transactions ``[j * T, (j + 1) * T)``, ``T`` the
    transactions over the shards rounded up to a word, as a CSR slice, and
    scatters them into its own word slice on the device.  The per-shard
    item counts merge by one ``psum``; the host reads the supports once,
    ranks the frequent items (:func:`sort_items`) and the devices gather
    those rows in place.  With ``filter_txns`` each shard drops its own
    transactions left with no frequent item and every shard is padded to
    the widest with zero words; ``n_txn`` is then the number kept.  Each
    shard's width is a multiple of the kernel's word tile
    (:func:`_shard_words`).  Supports and ``n_incidences`` count distinct
    (transaction, item) pairs, as :func:`build_vertical` does.
    """
    csr = (transactions if isinstance(transactions, bm.CSR)
           else bm.to_csr(transactions))
    offsets, items = bm.check_csr(csr, n_items)
    n_txn = offsets.size - 1
    shards = int(mesh.shape[axis])
    t_data = bm.WORD_BITS * bm.n_words(-(-n_txn // shards))
    t_shard = bm.WORD_BITS * _shard_words(t_data)
    local, flat = _shard_slices(offsets, items, shards, t_shard)
    split = NamedSharding(mesh, P(axis, None))
    dense, sup_dev, kept_dev = _scatter_program(mesh, axis, int(n_items))(
        jax.device_put(local, split), jax.device_put(flat, split),
        jax.device_put(np.int32(min_sup), NamedSharding(mesh, P())))
    sup_all, kept = jax.device_get((sup_dev, kept_dev))
    sup_all = sup_all.astype(np.int64)
    freq_items = np.nonzero(sup_all >= int(min_sup))[0].astype(np.int64)
    supports = sup_all[freq_items]
    perm = sort_items(freq_items, supports, order)
    n_kept = int(kept.sum())
    drop = filter_txns and n_kept < n_txn
    t_out = (min(t_shard, bm.WORD_BITS * _shard_words(
        _stable_size(n_kept, shards, int(kept.max()), bm.WORD_BITS)))
        if drop else t_shard)
    rows = jax.device_put(freq_items[perm].astype(np.int32),
                          NamedSharding(mesh, P()))
    packed = _pack_program(mesh, axis, t_out // bm.WORD_BITS, drop)(dense,
                                                                     rows)
    return VerticalDB(
        bitmaps=packed,
        items=freq_items[perm],
        supports=supports[perm],
        n_txn=n_kept if drop else n_txn,
        order=order,
        n_incidences=int(sup_all.sum()),
        host_bitmap_bytes=0,
        psum_calls=1,
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
