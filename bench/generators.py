"""Seeded transaction databases at the paper's published shapes.

The benchmark's own copy of the generator the configurations use, written
to run in set-up with vectorised NumPy.  A database is held
as its (transaction, item) incidence pairs sorted by transaction then item;
the transactions users hand to ``mine`` and the reference's bitmaps are
both read from it.

* :func:`quest` -- the IBM Quest process (Agrawal & Srikant, VLDB'94, 2.4.3):
  a pool of potentially large itemsets with exponential weights and
  per-itemset corruption levels; each transaction is filled with picks from
  the pool until it reaches its Poisson length.

A run's databases are relabelings of one base database (:func:`variant`):
the seed permutes item ids and transaction order, so every seed gives the
program the same sizes -- the same frequent items, the same candidate
pairs at every level, the same kernel shapes -- with different inputs and
different answers.
"""
from __future__ import annotations

import numpy as np

__all__ = ["Database", "quest", "base_database", "variant"]

MAX_PICKS = 40          # pattern picks after which a transaction stops short


class Database:
    """Incidence pairs ``(txn[i], item[i])``, sorted by transaction, then
    item, with no duplicates; ``n_txn`` rows over ``n_items`` items."""

    def __init__(self, txn: np.ndarray, item: np.ndarray, n_txn: int,
                 n_items: int):
        self.txn = np.asarray(txn, np.int64)
        self.item = np.asarray(item, np.int64)
        self.n_txn = int(n_txn)
        self.n_items = int(n_items)

    @classmethod
    def from_pairs(cls, txn, item, n_txn: int, n_items: int) -> "Database":
        order = np.lexsort((item, txn))
        txn, item = np.asarray(txn)[order], np.asarray(item)[order]
        keep = np.ones(txn.size, bool)
        keep[1:] = (txn[1:] != txn[:-1]) | (item[1:] != item[:-1])
        return cls(txn[keep], item[keep], n_txn, n_items)

    def transactions(self) -> list:
        """Sorted item-id lists, one per transaction: the form users hand to
        ``mine`` and ``StreamingMiner``."""
        flat = self.item.tolist()
        ptr = np.concatenate(
            [[0], np.cumsum(np.bincount(self.txn, minlength=self.n_txn))])
        ptr = ptr.tolist()
        return [flat[a:b] for a, b in zip(ptr[:-1], ptr[1:])]

    def supports(self) -> np.ndarray:
        return np.bincount(self.item, minlength=self.n_items)


def quest(rng: np.random.Generator, n_txn: int, n_items: int,
          avg_txn_len: float, avg_pattern_len: float, n_patterns: int,
          correlation: float, corruption_mean: float,
          corruption_sd: float) -> Database:
    """A Quest database ``T<avg_txn_len>I<avg_pattern_len>D<n_txn>``.

    Each pattern takes an exponentially distributed fraction (mean
    ``correlation``) of its items from the pattern before it.  Each pick of
    a pattern drops items while a uniform draw stays below the pattern's
    corruption level, then adds the rest in order until the transaction
    reaches its length; a transaction stops after ``MAX_PICKS`` picks.
    """
    sizes = np.clip(rng.poisson(avg_pattern_len, n_patterns), 1, n_items)
    patterns = [rng.choice(n_items, size=int(sizes[0]), replace=False)]
    for s in sizes[1:].tolist():
        prev = patterns[-1]
        n_shared = min(int(round(rng.exponential(correlation) * s)), s,
                       prev.size)
        shared = rng.choice(prev, size=n_shared, replace=False)
        rest = np.setdiff1d(np.arange(n_items), shared)
        fresh = rng.choice(rest, size=s - n_shared, replace=False)
        patterns.append(rng.permutation(np.concatenate([shared, fresh])))
    weights = rng.exponential(1.0, n_patterns)
    weights /= weights.sum()
    corrupt = np.clip(rng.normal(corruption_mean, corruption_sd, n_patterns),
                      0.0, 1.0)
    pat_len = sizes.astype(np.int64)
    pat_off = np.concatenate([[0], np.cumsum(pat_len)[:-1]])
    pat_items = np.concatenate(patterns).astype(np.int64)

    member = np.zeros((n_txn, n_items), bool)
    count = np.zeros(n_txn, np.int64)
    target = np.maximum(1, rng.poisson(avg_txn_len, n_txn))
    active = np.arange(n_txn)
    for _ in range(MAX_PICKS):
        if active.size == 0:
            break
        pick = rng.choice(n_patterns, size=active.size, p=weights)
        lens = pat_len[pick]
        # items dropped: the number of uniform draws below the corruption
        # level before the first one above it
        n_drop = rng.geometric(1.0 - np.minimum(corrupt[pick], 0.999)) - 1
        txn = np.repeat(active, lens)
        first = np.repeat(np.cumsum(lens) - lens, lens)
        pos = np.arange(lens.sum()) - first
        item = pat_items[np.repeat(pat_off[pick], lens) + pos]
        new = (pos >= np.repeat(n_drop, lens)) & ~member[txn, item]
        # rank each new item within its pick: items go in until the
        # transaction is full
        csum = np.cumsum(new)
        rank = csum - 1 - (csum[first] - new[first])
        room = np.repeat(target[active] - count[active], lens)
        add = new & (rank < room)
        member[txn[add], item[add]] = True
        count += np.bincount(txn[add], minlength=n_txn)
        active = active[count[active] < target[active]]
    empty = np.nonzero(count == 0)[0]
    member[empty, rng.integers(n_items, size=empty.size)] = True
    txn, item = np.nonzero(member)
    return Database(txn, item, n_txn, n_items)


def base_database(config: dict, n_txn: int = 0) -> Database:
    """The configuration's base database: its ``dataset`` shape drawn by its
    ``generator`` (``n_txn`` transactions, by default the dataset's)."""
    data, gen = config["dataset"], config["generator"]
    n_txn = int(n_txn or data["n_txn"])
    rng = np.random.default_rng(int(gen["seed"]))
    if gen["kind"] == "quest":
        return quest(rng, n_txn, int(data["n_items"]),
                     float(data["avg_txn_len"]), float(data["avg_pattern_len"]),
                     int(gen["n_patterns"]), float(gen["correlation"]),
                     float(gen["corruption_mean"]), float(gen["corruption_sd"]))
    raise ValueError(f"unknown generator kind {gen['kind']!r}")


def variant(base: Database, rng: np.random.Generator) -> Database:
    """A relabeling of ``base``: transactions shuffled, item ids permuted.

    The permutation keeps the relative order of any two items of equal
    support, so the (support, id) order every Eclat variant sorts by is the
    same as in ``base``: the work is identical, the answer is relabeled
    (``item_map``, for :func:`bench.reference.relabel`).
    """
    support = base.supports()
    new_id = rng.permutation(base.n_items)
    order = np.lexsort((np.arange(base.n_items), support))
    ties = np.split(order, np.nonzero(np.diff(support[order]))[0] + 1)
    for group in ties:                       # each group in ascending old id
        new_id[group] = np.sort(new_id[group])
    new_txn = rng.permutation(base.n_txn)
    db = Database.from_pairs(new_txn[base.txn], new_id[base.item],
                             base.n_txn, base.n_items)
    db.item_map = new_id                     # base item id -> this one's
    return db
