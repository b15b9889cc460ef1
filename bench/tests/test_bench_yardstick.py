"""The benchmark's own generators, reference miner and byte count."""
import itertools
import json
import os

import numpy as np
import pytest

import _paths
from bench import generators, needed_bytes, reference


def _config(name):
    with open(os.path.join(_paths.ROOT, "bench", "configs", f"{name}.json")) as f:
        return json.load(f)


def _db(rows, n_items):
    txn = [i for i, r in enumerate(rows) for _ in r]
    item = [x for r in rows for x in r]
    return generators.Database.from_pairs(txn, item, len(rows), n_items)


def test_reference_matches_brute_force():
    rng = np.random.default_rng(3)
    rows = [sorted(set(rng.integers(0, 7, size=rng.integers(1, 6)).tolist()))
            for _ in range(60)]
    db = _db(rows, 7)
    want = {}
    for k in range(1, 8):
        for combo in itertools.combinations(range(7), k):
            sup = sum(1 for r in rows if set(combo) <= set(r))
            if sup >= 12:
                want[combo] = sup
    assert reference.mine(db, 0.2) == want
    assert reference.compare(reference.mine(db, 0.2), want) == 0


def test_compare_counts_missing_extra_and_wrong_support():
    want = {(1,): 5, (2,): 4, (1, 2): 3}
    got = {(1,): 5, (2,): 3, (3,): 9}
    assert reference.compare(got, want) == 3


def test_abs_min_sup_rounds_up_exactly():
    assert reference.abs_min_sup(0.01, 100_000) == 1000
    assert reference.abs_min_sup(0.7, 3196) == 2238
    assert reference.abs_min_sup(0.07, 100) == 7
    assert reference.abs_min_sup(5, 100) == 5
    with pytest.raises(ValueError):
        reference.abs_min_sup(0, 10)


def test_needed_bytes_hand_worked():
    # supports 0:3 1:3 2:2 3:1 at min_sup 2; order by (support, id): 2, 0, 1
    rows = [[0, 1, 2], [0, 1, 2], [0, 1], [3]]
    answer = reference.mine(_db(rows, 4), 2)
    assert answer == {(0,): 3, (1,): 3, (2,): 2, (0, 1): 3, (0, 2): 2,
                      (1, 2): 2, (0, 1, 2): 2}
    # level 2: the 3 frequent pairs; level 3: the class {2,0},{2,1} -> 1
    assert needed_bytes.candidates_per_level(answer) == {2: 3, 3: 1}
    # 3 rows hold a frequent item -> 1 word of 4 bytes; 4 candidates read
    # 2 rows each, 4 itemsets of length >= 2 write one each
    assert needed_bytes.needed_bytes(answer, 3) == 2 * 4 * 4 + 4 * 4


def test_needed_bytes_counts_the_level_where_none_survive():
    answer = {(0,): 5, (1,): 5, (2,): 5, (0, 1): 3, (0, 2): 3, (1, 2): 3}
    # the pairs under rank 0 form a class of two: one level-3 candidate,
    # counted though no triple is frequent
    assert needed_bytes.candidates_per_level(answer) == {2: 3, 3: 1}


def test_generator_shapes_and_determinism():
    t10 = _config("T10I4D100K")
    a = generators.base_database(t10, 20_000)
    b = generators.base_database(t10, 20_000)
    assert np.array_equal(a.txn, b.txn) and np.array_equal(a.item, b.item)
    lens = np.bincount(a.txn, minlength=a.n_txn)
    assert lens.min() >= 1 and 9.5 < lens.mean() < 10.5
    assert (a.supports() > 0).sum() > 800
    t40 = generators.base_database(_config("T40I10D100K"), 20_000)
    lens = np.bincount(t40.txn, minlength=t40.n_txn)
    assert t40.n_items == 1000 and 39 < lens.mean() < 41


def test_variant_relabels_without_changing_the_work():
    base = generators.base_database(_config("T40I10D100K"), 3000)
    v1 = generators.variant(base, np.random.default_rng(1))
    v2 = generators.variant(base, np.random.default_rng(2))
    a0, a1, a2 = (reference.mine(d, 0.02) for d in (base, v1, v2))
    assert max(len(k) for k in a0) >= 5
    assert a1 != a2
    levels = [sorted(np.bincount([len(k) for k in a]).tolist()) for a in (a0, a1, a2)]
    assert levels[0] == levels[1] == levels[2]
    assert (needed_bytes.candidates_per_level(a0)
            == needed_bytes.candidates_per_level(a1)
            == needed_bytes.candidates_per_level(a2))
    v1_again = generators.variant(base, np.random.default_rng(1))
    assert np.array_equal(v1.item, v1_again.item)


def test_relabeled_reference_is_the_reference_of_the_variant():
    base = generators.base_database(_config("T10I4D100K"), 4000)
    v = generators.variant(base, np.random.default_rng(7))
    want = reference.mine(v, 0.01)
    assert max(len(k) for k in want) >= 3
    assert reference.relabel(reference.mine(base, 0.01), v.item_map) == want
