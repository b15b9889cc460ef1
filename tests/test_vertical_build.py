"""The vertical build on flat incidence arrays against a per-bit reference.

Every variant's ``_build_db`` path, on the host and with a 4-device mesh
(the ``psum`` accumulator for v3+), must give the same ``VerticalDB`` and
``filter_reduction`` as setting each (transaction, item) bit one at a time,
then dropping infrequent rows and, for v2+, the transactions left with no
frequent item.
"""
import numpy as np
import pytest

from repro.core import bitmap as bm
from repro.core.eclat import VARIANTS, EclatConfig, _build_db, mine
from repro.core.vertical import build_vertical


def _reference(transactions, n_items, min_sup, filter_txns):
    """Per-bit reference: one Python loop over every bit, then the filter as
    a column selection of the dense matrix."""
    txns = [list(t) for t in transactions]
    n_txn = len(txns)
    dense = np.zeros((n_items, n_txn), dtype=bool)
    for tid, t in enumerate(txns):
        for it in set(int(i) for i in t):
            dense[it, tid] = True
    supports = dense.sum(axis=1).astype(np.int64)
    n_incidences = int(dense.sum())
    freq = supports >= min_sup
    items = np.nonzero(freq)[0].astype(np.int64)
    dense, supports = dense[freq], supports[freq]
    kept = n_txn
    if filter_txns:
        cols = dense.any(axis=0)
        dense, kept = dense[:, cols], int(cols.sum())
    perm = np.lexsort((items, supports))
    w = (kept + 31) // 32
    bitmaps = np.zeros((items.size, w), dtype=np.uint32)
    for r, row in enumerate(dense[perm]):
        for t in np.nonzero(row)[0]:
            bitmaps[r, t // 32] |= np.uint32(1 << int(t % 32))
    info = {"filter_reduction": 1.0 - kept / n_txn if n_txn else 0.0} if filter_txns else {}
    return bitmaps, items[perm], supports[perm], kept, n_incidences, info


def _random_txns(seed, n_txn, n_items):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, n_items, size=int(rng.integers(0, 6))).tolist()
            for _ in range(n_txn)]


def _cases():
    rng = np.random.default_rng(7)
    mixed = []
    for i, t in enumerate(_random_txns(3, 77, 9)):
        kind = i % 4
        mixed.append(t if kind == 0 else tuple(t) if kind == 1
                     else np.asarray(t, dtype=np.int64) if kind == 2 else set(t))
    return {
        # (transactions, n_items, min_sup)
        "duplicates": ([[1, 1, 2], [2, 2, 2], [0, 1, 0], [1, 2, 1], [3]] * 8, 5, 3),
        "empty_txns": ([[], [0, 1], [], [1, 2], [0], []] * 7, 4, 2),
        "filter_empties": ([[0, 1], [5], [6, 7], [1, 2], [0, 2], [7]] * 6 + [[4]], 8, 12),
        "unsorted": ([rng.permutation(6)[: 1 + i % 5].tolist() for i in range(50)], 6, 9),
        "tuple_ndarray_set": (mixed, 9, 8),
        "n_txn_45": (_random_txns(11, 45, 7), 7, 6),
        "n_txn_257": (_random_txns(12, 257, 12), 12, 40),
        "no_frequent_item": (_random_txns(13, 40, 6), 6, 1000),
        "zero_txns": ([], 5, 1),
    }


CASES = _cases()


@pytest.fixture(scope="module")
def data_mesh(host_devices):
    from repro.dist.compat import make_mesh
    return make_mesh((4,), ("data",))


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("where", ["host", "mesh"])
@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_build_db_matches_per_bit_reference(variant, where, case, request):
    txns, n_items, min_sup = CASES[case]
    spec = VARIANTS[variant]
    mesh = request.getfixturevalue("data_mesh") if where == "mesh" else None
    db, info = _build_db(txns, n_items, min_sup, spec, mesh)
    bitmaps, items, supports, n_txn, n_inc, want_info = _reference(
        txns, n_items, min_sup, spec["filter_txns"])
    assert db.bitmaps.dtype == np.uint32
    np.testing.assert_array_equal(db.bitmaps, bitmaps)
    np.testing.assert_array_equal(db.items, items)
    np.testing.assert_array_equal(db.supports, supports)
    assert db.n_txn == n_txn
    assert db.n_incidences == n_inc
    assert info == want_info
    db.validate()


def test_filter_engages_where_a_transaction_loses_every_item():
    txns, n_items, min_sup = CASES["filter_empties"]
    db, info = _build_db(txns, n_items, min_sup, VARIANTS["v4"], None)
    assert 0.0 < info["filter_reduction"] < 1.0
    assert db.n_txn < len(txns)


def test_generator_transactions_pack_like_lists():
    txns = _random_txns(5, 70, 8)
    gen = (iter(t) for t in txns)
    np.testing.assert_array_equal(bm.pack_transactions(gen, 8),
                                  bm.pack_transactions(txns, 8))


@pytest.mark.parametrize("bad", [-1, 6, 1000])
@pytest.mark.parametrize("route", ["mine_v4", "accumulated_mesh", "pack"])
def test_item_out_of_range_names_the_transaction(route, bad, request):
    txns = [[0, 1], [2], [1, bad, 3], [4]]
    with pytest.raises(ValueError, match=r"txn 2 has item outside \[0, 6\)"):
        if route == "mine_v4":
            mine(txns, 6, EclatConfig(min_sup=1, variant="v4", backend="jnp"))
        elif route == "accumulated_mesh":
            build_vertical(txns, 6, 1, filter_txns=True,
                           mesh=request.getfixturevalue("data_mesh"))
        else:
            bm.pack_transactions(txns, 6)


def test_popcount_matches_swar_on_random_words():
    rng = np.random.default_rng(0)
    words = rng.integers(0, 2**32, size=(37, 65), dtype=np.uint32)
    words[0, :3] = [0, 0xFFFFFFFF, 1]
    words[1] = 0xFFFFFFFF
    want = bm._popcount_swar(words)
    np.testing.assert_array_equal(bm.popcount_np(words), want)
    assert bm.popcount_np(words).dtype == np.int64
    np.testing.assert_array_equal(bm.support_np(words), want.sum(axis=-1))
    assert bm.support_np(words)[1] == 65 * 32
    assert bm.popcount_np(np.uint32(0xFFFFFFFF)) == 32
    assert bm.popcount_np(np.uint32(0)) == 0
