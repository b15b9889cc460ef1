"""HBM bytes that Eclat over dense bitmap rows needs for one mine.

The yardstick of the intersection kernel's roofline share.  It counts the
algorithm's work, not the implementation's: it is computed from the
reference answer and the database's shape, and reads no counter of the
program.

* A row is ``W = ceil(n_rows / 32)`` 32-bit words, where ``n_rows`` is the
  number of transactions that hold at least one frequent item (the
  paper's filtered transactions).
* Items are ranked by (support, id) ascending, the paper's total order, and
  an itemset is the sequence of its items' ranks.
* Level 2: the candidates are the frequent pairs, because the triangular
  co-occurrence matrix filters them before any row is intersected.
* Level k >= 3: the candidates are, over each class of frequent
  (k-1)-itemsets that share their first k-2 items, every pair in the class
  -- up to and including the first level at which none survives.
* Each candidate reads its two parent rows; each frequent itemset of length
  two or more writes its row.

A representation that reads fewer bytes than two dense rows per candidate
(sparse tidsets, a parent row kept on chip across its class) needs a
benchmark change to re-base this count.
"""
from __future__ import annotations

from collections import Counter
from typing import Dict, Tuple

import numpy as np

__all__ = ["row_bytes", "candidates_per_level", "needed_bytes"]

WORD_BYTES = 4


def row_bytes(n_rows: int) -> int:
    return -(-int(n_rows) // 32) * WORD_BYTES


def candidates_per_level(answer: Dict[Tuple[int, ...], int]) -> Dict[int, int]:
    """``{k: candidate pairs at level k}`` for k >= 2, in the (support, id)
    order; the level after the deepest frequent one is included."""
    single = {key[0]: sup for key, sup in answer.items() if len(key) == 1}
    rank = {item: r for r, item in
            enumerate(sorted(single, key=lambda i: (single[i], i)))}
    by_len: Dict[int, list] = {}
    for key in answer:
        by_len.setdefault(len(key), []).append(
            tuple(sorted(rank[i] for i in key)))
    out = {}
    if 2 in by_len:
        out[2] = len(by_len[2])
    k = 3
    while k - 1 in by_len:
        classes = Counter(s[:-1] for s in by_len[k - 1])
        n = sum(c * (c - 1) // 2 for c in classes.values())
        if n == 0:
            break
        out[k] = n
        k += 1
    return out


def needed_bytes(answer: Dict[Tuple[int, ...], int], n_rows: int) -> int:
    """Bytes read and written by the level >= 2 intersections of one mine."""
    rb = row_bytes(n_rows)
    reads = 2 * rb * sum(candidates_per_level(answer).values())
    writes = rb * sum(1 for key in answer if len(key) >= 2)
    return reads + writes


def rows_with_frequent_item(txn: np.ndarray, item: np.ndarray,
                            frequent: np.ndarray) -> int:
    """Transactions (incidence pairs ``txn``/``item``) holding at least one
    item of ``frequent``."""
    return int(np.unique(txn[np.isin(item, frequent)]).size)
