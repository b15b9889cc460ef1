"""Itemset store, reconstruction and association-rule generation.

Frontier rows carry (parent pointer, last item) only; this module turns the
per-level row records into explicit itemsets (the ``saveAsTextFile`` analogue)
and implements ARM step 2 (confident rules) for completeness.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Iterator, List, Tuple

import numpy as np

__all__ = ["LevelRecord", "ItemsetStore", "generate_rules"]


@dataclasses.dataclass
class LevelRecord:
    """Compact record of one mined level (host-side, bitmap-free)."""

    k: int
    parent: np.ndarray      # (P,) row index into level k-1 (-1 at k == 1)
    item_rank: np.ndarray   # (P,) frequent-item rank of the last item
    support: np.ndarray     # (P,)
    partition: np.ndarray   # (P,)


class ItemsetStore:
    """Accumulates LevelRecords and reconstructs explicit itemsets.

    Reconstruction is per level, over arrays: level k's paths are an
    ``(P_k, k)`` matrix of item ids, its parents' rows with the last item
    appended, sorted along each row once to give the keys. ``itemsets()``
    is in level order, then row order within a level; ``support_map()``
    holds the same pairs in the same order. Ids and supports are Python
    ``int``.
    """

    def __init__(self, item_ids: np.ndarray):
        self._item_ids = np.asarray(item_ids, dtype=np.int64)
        self.levels: List[LevelRecord] = []

    def add_level(self, rec: LevelRecord) -> None:
        if self.levels and rec.k != self.levels[-1].k + 1:
            raise ValueError("levels must be added in order")
        self.levels.append(rec)

    @property
    def counts(self) -> List[int]:
        return [int(l.parent.shape[0]) for l in self.levels]

    @property
    def total(self) -> int:
        return int(sum(self.counts))

    def _level_keys(self) -> Iterator[Tuple[Iterator[Tuple[int, ...]], List[int]]]:
        """Per level: (sorted item-id tuple of each row, support of each row)."""
        paths = np.zeros((0, 0), np.int64)
        for rec in self.levels:
            last = self._item_ids[np.asarray(rec.item_rank)][:, None]
            if rec.k == 1:
                paths = last
            else:
                paths = np.concatenate([paths[np.asarray(rec.parent)], last], axis=1)
            keys = map(tuple, np.sort(paths, axis=1).tolist())
            yield keys, np.asarray(rec.support).tolist()

    def itemsets(self) -> List[Tuple[Tuple[int, ...], int]]:
        """All frequent itemsets as (sorted item-id tuple, support)."""
        out: List[Tuple[Tuple[int, ...], int]] = []
        for keys, supports in self._level_keys():
            out.extend(zip(keys, supports))
        return out

    def support_map(self) -> Dict[Tuple[int, ...], int]:
        out: Dict[Tuple[int, ...], int] = {}
        for keys, supports in self._level_keys():
            out.update(zip(keys, supports))
        return out


def generate_rules(
    support_map: Dict[Tuple[int, ...], int], min_conf: float
) -> List[Tuple[Tuple[int, ...], Tuple[int, ...], float, int]]:
    """ARM step 2: rules X => Y with conf = sup(X∪Y)/sup(X) >= min_conf.

    Returns (antecedent, consequent, confidence, support) tuples.
    """
    from itertools import combinations

    rules = []
    for itemset, sup in support_map.items():
        k = len(itemset)
        if k < 2:
            continue
        for r in range(1, k):
            for ante in combinations(itemset, r):
                sup_a = support_map.get(tuple(sorted(ante)))
                if not sup_a:
                    continue
                conf = sup / sup_a
                if conf >= min_conf:
                    cons = tuple(sorted(set(itemset) - set(ante)))
                    rules.append((tuple(sorted(ante)), cons, float(conf), int(sup)))
    return rules
