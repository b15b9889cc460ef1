"""Plain frequent-itemset miner: the answer every cell is compared with.

A straightforward level-wise miner over NumPy bitmaps, written apart from
the program under test and importing none of it.  Items are taken in
ascending id order; the ``k``-itemsets that share their first ``k - 1``
items form a class, every pair within a class is a candidate, and a
candidate's support is the popcount of its two parents' AND.  Supports are
exact int64 counts and the threshold is ``ceil(min_sup * n_txn)`` computed
exactly, so the answer is the complete set of itemsets at or above it.
"""
from __future__ import annotations

import math
from fractions import Fraction
from typing import Dict, Tuple

import numpy as np

from .generators import Database

__all__ = ["abs_min_sup", "bitmaps", "mine", "relabel", "compare"]

CHUNK_PAIRS = 256


def abs_min_sup(min_sup, n_txn: int) -> int:
    """A fraction in (0, 1] of ``n_txn``, rounded up exactly; a whole
    number above 1 is an absolute count."""
    f = Fraction(str(min_sup))
    if 0 < f <= 1:
        return max(1, math.ceil(f * n_txn))
    if f > 1 and f.denominator == 1:
        return int(f)
    raise ValueError(f"min_sup must be a fraction in (0, 1] or a count, "
                     f"got {min_sup!r}")


def bitmaps(db: Database) -> np.ndarray:
    """``(n_items, ceil(n_txn / 64))`` uint64 tidset bitmaps."""
    dense = np.zeros((db.n_items, db.n_txn), bool)
    dense[db.item, db.txn] = True
    packed = np.packbits(dense, axis=1, bitorder="little")
    pad = (-packed.shape[1]) % 8
    packed = np.pad(packed, ((0, 0), (0, pad)))
    return np.ascontiguousarray(packed).view(np.uint64)


def _expand(bits: np.ndarray, left: np.ndarray, right: np.ndarray,
            thr: int):
    """The candidates ``(left[i], right[i])`` with support >= ``thr``:
    their indices, supports and bitmaps (each AND computed once)."""
    keep, sups, rows = [], [], []
    for s in range(0, left.size, CHUNK_PAIRS):
        l, r = left[s:s + CHUNK_PAIRS], right[s:s + CHUNK_PAIRS]
        child = bits[l] & bits[r]
        sup = np.bitwise_count(child).sum(axis=1, dtype=np.int64)
        ok = np.nonzero(sup >= thr)[0]
        keep.append(ok + s)
        sups.append(sup[ok])
        rows.append(child[ok])
    if not keep:
        return (np.zeros(0, np.int64), np.zeros(0, np.int64),
                bits[:0])
    return np.concatenate(keep), np.concatenate(sups), np.concatenate(rows)


def _class_pairs(sets: np.ndarray):
    """All (i, j), i < j, of rows whose itemsets agree on all but the last
    item; rows are in lexicographic order, so each class is a run."""
    m, k = sets.shape
    if m < 2:
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    if k == 1:
        bounds = np.array([0, m])
    else:
        change = np.any(sets[1:, :-1] != sets[:-1, :-1], axis=1)
        bounds = np.concatenate([[0], np.nonzero(change)[0] + 1, [m]])
    left, right = [], []
    for a, b in zip(bounds[:-1].tolist(), bounds[1:].tolist()):
        if b - a > 1:
            i, j = np.triu_indices(b - a, k=1)
            left.append(i + a)
            right.append(j + a)
    if not left:
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    return np.concatenate(left), np.concatenate(right)


def mine(db: Database, min_sup) -> Dict[Tuple[int, ...], int]:
    """Every itemset with support >= ``abs_min_sup(min_sup, db.n_txn)``,
    as ``{sorted item-id tuple: support}``."""
    thr = abs_min_sup(min_sup, db.n_txn)
    item_sup = db.supports()
    freq = np.nonzero(item_sup >= thr)[0]
    answer = {(int(i),): int(item_sup[i]) for i in freq}
    sets = freq[:, None]
    bits = bitmaps(db)[freq]
    while sets.shape[0] > 1:
        left, right = _class_pairs(sets)
        keep, sup, bits = _expand(bits, left, right, thr)
        left, right = left[keep], right[keep]
        sets = np.concatenate([sets[left], sets[right][:, -1:]], axis=1)
        answer.update(zip(map(tuple, sets.tolist()), sup.tolist()))
    return answer


def relabel(answer: Dict, item_map: np.ndarray) -> Dict:
    """``answer`` with every item id ``i`` renamed ``item_map[i]``: the
    answer of a database whose items were renamed so (and whose
    transactions were reordered, which changes no support)."""
    m = item_map.tolist()
    return {tuple(sorted(m[i] for i in k)): v for k, v in answer.items()}


def compare(got: Dict, want: Dict) -> int:
    """Itemsets that are missing, extra, or carry another support."""
    wrong = sum(1 for key, sup in want.items() if got.get(key) != sup)
    return wrong + sum(1 for key in got if key not in want)
