"""ItemsetStore reconstruction: the per-level array build gives exactly the
itemsets and support map of a plain per-row walk of the parent pointers,
in the same order and with Python ``int`` ids and supports."""
import numpy as np
import pytest

from repro.core.itemsets import ItemsetStore, LevelRecord


def _rowwise_itemsets(store):
    """The per-row reconstruction: follow each row's parent pointer."""
    out = []
    prev_paths = []
    for rec in store.levels:
        paths = []
        for r in range(rec.parent.shape[0]):
            item = int(store._item_ids[rec.item_rank[r]])
            if rec.k == 1:
                path = (item,)
            else:
                path = prev_paths[int(rec.parent[r])] + (item,)
            paths.append(path)
            out.append((tuple(sorted(path)), int(rec.support[r])))
        prev_paths = paths
    return out


def _level(k, parent, item_rank, support, support_dtype=np.int64):
    parent = np.asarray(parent, np.int64)
    return LevelRecord(k=k, parent=parent,
                       item_rank=np.asarray(item_rank, np.int64),
                       support=np.asarray(support, support_dtype),
                       partition=np.zeros(parent.shape[0], np.int64))


def _level1(n, supports, support_dtype=np.int64):
    return _level(1, np.full(n, -1), np.arange(n), supports, support_dtype)


def _random_lattice(seed, n_levels, support_dtype):
    """A store of ``n_levels`` levels: each row extends a random parent row
    by an item of higher rank, with item ids drawn out of rank order."""
    rng = np.random.default_rng(seed)
    n1 = 40
    item_ids = rng.choice(5000, size=n1, replace=False)
    store = ItemsetStore(item_ids)
    store.add_level(_level1(n1, rng.integers(1, 10**6, n1), support_dtype))
    last_rank = np.arange(n1)
    for k in range(2, n_levels + 1):
        can_grow = np.nonzero(last_rank < n1 - 1)[0]
        parent = np.sort(rng.choice(can_grow, size=min(60, 3 * can_grow.size)))
        item_rank = rng.integers(last_rank[parent] + 1, n1)
        store.add_level(_level(k, parent, item_rank,
                               rng.integers(1, 10**6, parent.size), support_dtype))
        last_rank = item_rank
    return store


def _hand_lattice():
    # items by rank are 7, 3, 300, 1, so sorting a rank path by id
    # reorders it: level 3's rows are (7, 3, 1) and (7, 300, 1)
    store = ItemsetStore(np.array([7, 3, 300, 1]))
    store.add_level(_level1(4, [50, 40, 30, 20]))
    store.add_level(_level(2, [0, 0, 1], [1, 2, 3], [25, 24, 15]))
    store.add_level(_level(3, [0, 1], [3, 3], [10, 9]))
    return store


def _build(case):
    if case == "empty":
        return ItemsetStore(np.zeros(0, np.int64))
    if case == "level1_only":
        store = ItemsetStore(np.array([11, 2, 5]))
        store.add_level(_level1(3, [9, 8, 7]))
        return store
    if case == "zero_row_level1":
        store = ItemsetStore(np.zeros(0, np.int64))
        store.add_level(_level1(0, []))
        return store
    if case == "zero_row_level2":
        store = ItemsetStore(np.array([4, 9]))
        store.add_level(_level1(2, [6, 5]))
        store.add_level(_level(2, [], [], []))
        return store
    if case == "ids_above_256":
        store = ItemsetStore(np.array([70000, 257, 1 << 40, 999]))
        store.add_level(_level1(4, [4, 3, 2, 1]))
        store.add_level(_level(2, [0, 1, 2], [2, 2, 3], [3, 2, 1]))
        return store
    if case == "hand_3_levels":
        return _hand_lattice()
    if case.startswith("random_6_levels_"):
        dtype = np.int32 if case.endswith("int32") else np.int64
        seed = int(case.split("_")[3])
        return _random_lattice(seed, 6, dtype)
    raise AssertionError(case)


@pytest.mark.parametrize("case", [
    "empty", "level1_only", "zero_row_level1", "zero_row_level2",
    "ids_above_256", "hand_3_levels",
    "random_6_levels_0_int32", "random_6_levels_0_int64",
    "random_6_levels_1_int32", "random_6_levels_2_int64",
])
def test_reconstruction_matches_rowwise_walk(case):
    store = _build(case)
    want = _rowwise_itemsets(store)

    got = store.itemsets()
    assert got == want
    for itemset, support in got:
        assert type(itemset) is tuple
        assert all(type(i) is int for i in itemset)
        assert type(support) is int

    smap = store.support_map()
    assert list(smap.items()) == list(dict(want).items())
    for itemset, support in smap.items():
        assert all(type(i) is int for i in itemset)
        assert type(support) is int
    assert len(got) == store.total
