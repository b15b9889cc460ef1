"""Spark-accumulator analogue (EclatV3's vertical-DB build).

Spark accumulators are add-only shared variables merged associatively on the
driver.  The SPMD analogue is a per-shard partial value combined with an
associative collective — ``psum`` (bit-disjoint partials make add == or) or an
explicit OR tree on the host.  EclatV3 builds the (item -> tidset) hashmap as
an accumulator; here each shard owns a contiguous block of transaction ids,
scatters its own bits into a zero-initialised packed matrix, and the partials
are OR-merged.
"""
from __future__ import annotations

from typing import Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..dist.compat import shard_map
from . import bitmap as bm
from .vertical import VerticalDB, sort_items

__all__ = ["HostAccumulator", "build_vertical_accumulated"]


class HostAccumulator:
    """Add-only accumulator with an associative merge, driver-readable only
    (mirrors the Spark contract: workers add, driver reads)."""

    def __init__(self, zero, merge):
        self._value = zero
        self._merge = merge
        self._adds = 0

    def add(self, partial) -> None:
        self._value = self._merge(self._value, partial)
        self._adds += 1

    def value(self):
        return self._value

    @property
    def n_adds(self) -> int:
        return self._adds


def _partial_bitmap(chunk: Sequence[Sequence[int]], tid_offset: int, n_items: int, w: int) -> np.ndarray:
    packed = np.zeros((n_items, w), dtype=np.uint64)
    for local, items in enumerate(chunk):
        tid = tid_offset + local
        for it in set(int(i) for i in items):
            packed[it, tid // bm.WORD_BITS] |= np.uint64(1) << np.uint64(tid % bm.WORD_BITS)
    return packed.astype(np.uint32)


def build_vertical_accumulated(
    transactions: Sequence[Sequence[int]],
    n_items: int,
    min_sup: int,
    order: str = "support_asc",
    n_shards: int = 4,
    mesh: Optional[jax.sharding.Mesh] = None,
    axis: str = "data",
) -> VerticalDB:
    """EclatV3 Phase-3: accumulator-built vertical DB.

    Host mode (``mesh=None``) partitions the transactions into ``n_shards``
    chunks whose partial bitmaps are OR-merged through a
    :class:`HostAccumulator`.  Device mode runs the merge as a
    ``shard_map``+``psum`` (partials are bit-disjoint, so add == or) over the
    given mesh axis — the honest multi-chip path.
    """
    n_txn = len(transactions)
    w = bm.n_words(n_txn)
    if mesh is not None:
        d = mesh.shape[axis]
        bounds = np.linspace(0, n_txn, d + 1).astype(int)
        partials = np.stack(
            [
                _partial_bitmap(transactions[bounds[i]: bounds[i + 1]], int(bounds[i]), n_items, w)
                for i in range(d)
            ]
        )

        def _merge(part):  # part: (1, n_items, w) per shard
            return jax.lax.psum(part[0], axis)

        merged = jax.jit(
            shard_map(
                _merge, mesh=mesh, in_specs=P(axis, None, None), out_specs=P()
            )
        )(jnp.asarray(partials))
        packed = np.asarray(merged).astype(np.uint32)
    else:
        n_shards = max(1, min(n_shards, max(n_txn, 1)))
        bounds = np.linspace(0, n_txn, n_shards + 1).astype(int)
        acc = HostAccumulator(
            zero=np.zeros((n_items, w), dtype=np.uint32), merge=np.bitwise_or
        )
        for i in range(n_shards):
            acc.add(_partial_bitmap(transactions[bounds[i]: bounds[i + 1]], int(bounds[i]), n_items, w))
        packed = acc.value()

    supports = bm.support_np(packed)
    n_incidences = int(supports.sum())
    freq_mask = supports >= int(min_sup)
    items = np.nonzero(freq_mask)[0].astype(np.int64)
    packed = packed[freq_mask]
    supports = supports[freq_mask]
    perm = sort_items(items, supports, order)
    return VerticalDB(
        bitmaps=packed[perm], items=items[perm], supports=supports[perm],
        n_txn=n_txn, order=order, n_incidences=n_incidences,
    )
