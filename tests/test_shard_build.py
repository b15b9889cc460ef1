"""Array (CSR) input and the word-sharded mine path on the 4 host devices
``conftest.py`` forces (DESIGN.md §7).

* ``mine()`` on a CSR pair gives the answer of ``mine()`` on the same
  transactions as lists, on every backend; a malformed CSR raises.
* The per-shard device build: each shard's word slice is the host build of
  that shard's own transactions (rows and supports of the whole database,
  the v2+ filter applied per shard), padded with zero words; no bitmap
  byte crosses the host.
* The sharded level-2 pass counts what the one-device pass counts.
* An expand call's per-device block stays under the engine's byte budget.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import CSR, EclatConfig, bruteforce_fim, mine
from repro.core import bitmap as bm
from repro.core import engine as eng
from repro.core.triangular import cooccurrence_counts
from repro.core.vertical import build_vertical_sharded, sort_items
from repro.dist.compat import make_mesh


def _mesh(n=4):
    return make_mesh((n,), ("data",), devices=jax.devices()[:n])


def _grid():
    return make_mesh((2, 2), ("class", "data"), devices=jax.devices()[:4])


def _per_shard(n_txn, shards=4):
    """Transactions a shard holds: the shards' share, in whole words."""
    return 32 * -(-(-(-n_txn // shards)) // 32)


def _random_txns(seed, n_txn, n_items, dense_items=()):
    """Random baskets, half of them holding ``dense_items``; some empty,
    some with repeated or unsorted items."""
    rng = np.random.default_rng(seed)
    txns = []
    for _ in range(n_txn):
        t = rng.integers(0, n_items, size=int(rng.integers(0, 7))).tolist()
        if dense_items and rng.random() < 0.5:
            t += list(dense_items)
        txns.append(t[::-1] if rng.random() < 0.3 else t)
    return txns


# ---------------------------------------------------------------------------
# CSR input
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["jnp", "pallas", "tidsharded"])
@pytest.mark.parametrize("seed,n_txn", [(1, 150), (2, 401), (3, 64)])
def test_mine_on_csr_equals_mine_on_lists(backend, seed, n_txn):
    txns = _random_txns(seed, n_txn, 9, dense_items=(0, 1, 2))
    mesh = _mesh() if backend == "tidsharded" else None
    cfg = EclatConfig(min_sup=0.1, variant="v4", backend=backend)
    got = mine(bm.to_csr(txns), 9, cfg, mesh=mesh)
    want = mine(txns, 9, cfg, mesh=mesh)
    assert got.support_map() == want.support_map()
    assert got.support_map() == bruteforce_fim(
        [sorted(set(t)) for t in txns], min_sup=got.stats["abs_min_sup"])
    assert got.stats["counts"]["incidences"] == want.stats["counts"]["incidences"]


def test_to_csr_round_trips_the_transactions():
    txns = [[3, 1], [], [2, 2, 0], [4]]
    csr = bm.to_csr(txns)
    assert csr.offsets.tolist() == [0, 2, 2, 5, 6]
    assert csr.items.tolist() == [3, 1, 2, 2, 0, 4]
    assert bm.n_transactions(csr) == 4 == bm.n_transactions(txns)


@pytest.mark.parametrize("offsets,items,match", [
    ([0, 3, 2, 4], [0, 1, 2, 3], "descend at txn 1"),
    ([0, 2, 4], [0, 1, 9, 1], "txn 1 has item outside"),
    ([0, 2, 4], [0, 1, -1, 1], "txn 1 has item outside"),
    ([0, 2, 5], [0, 1, 2, 3], "from 0 to the 4 items"),
    ([1, 2, 4], [0, 1, 2, 3], "from 0 to the 4 items"),
    ([0, 2, 4], [[0, 1], [2, 3]], "1-D integer"),
])
@pytest.mark.parametrize("backend", ["pallas", "tidsharded"])
def test_malformed_csr_raises(offsets, items, match, backend):
    csr = CSR(np.asarray(offsets), np.asarray(items))
    mesh = _mesh() if backend == "tidsharded" else None
    with pytest.raises(ValueError, match=match):
        mine(csr, 5, EclatConfig(min_sup=1, backend=backend), mesh=mesh)


# ---------------------------------------------------------------------------
# per-shard device build
# ---------------------------------------------------------------------------

def _host_shard(txns, rows, filter_txns, width):
    """The host build of one shard's transactions: bit ``t`` of row ``r``
    set where local transaction ``t`` holds item ``rows[r]``, transactions
    with none of ``rows`` dropped under the filter, ``width`` words."""
    member = np.zeros((rows.size, len(txns)), bool)
    pos = {int(i): r for r, i in enumerate(rows)}
    for t, basket in enumerate(txns):
        for i in basket:
            if int(i) in pos:
                member[pos[int(i)], t] = True
    if filter_txns:
        member = member[:, member.any(axis=0)]
    out = np.zeros((rows.size, width), np.uint32)
    for r, t in zip(*np.nonzero(member)):
        out[r, t // 32] |= np.uint32(1 << int(t % 32))
    return out, member.shape[1]


CASES = {
    # (transactions, n_items, min_sup)
    "n_txn_1000": (_random_txns(4, 1000, 12, (0, 3)), 12, 60),
    "n_txn_150": (_random_txns(5, 150, 9, (1, 2)), 9, 15),
    "n_txn_45": (_random_txns(6, 45, 7), 7, 6),
    "filter_empties": ([[0, 1], [5], [6, 7], [1, 2], [0, 2], [7]] * 30
                       + [[4]], 8, 40),
    "zero_txns": ([], 5, 1),
    "no_frequent_item": (_random_txns(9, 40, 6), 6, 1000),
}


@pytest.mark.parametrize("filter_txns", [False, True])
@pytest.mark.parametrize("case", sorted(CASES))
def test_each_shard_is_the_host_build_of_its_transactions(case, filter_txns):
    txns, n_items, min_sup = CASES[case]
    mesh = _mesh()
    db = build_vertical_sharded(bm.to_csr(txns), n_items, min_sup,
                                filter_txns=filter_txns, mesh=mesh)
    sh = db.bitmaps.sharding
    assert isinstance(sh, NamedSharding) and sh.mesh == mesh
    if db.items.size:          # an empty array's spec reads P()
        assert sh.spec == P(None, "data")
    # supports and row order are the whole database's
    dense = np.zeros((n_items, len(txns)), bool)
    for t, basket in enumerate(txns):
        dense[list(basket), t] = True
    sup_all = dense.sum(axis=1)
    freq = np.nonzero(sup_all >= min_sup)[0]
    perm = sort_items(freq, sup_all[freq], "support_asc")
    np.testing.assert_array_equal(db.items, freq[perm])
    np.testing.assert_array_equal(db.supports, sup_all[freq][perm])
    assert db.n_incidences == int(dense.sum())
    assert db.host_bitmap_bytes == 0 and db.psum_calls == 1
    # each shard: its own contiguous block of whole words of transactions
    width = db.bitmaps.shape[1] // 4
    per_shard = _per_shard(len(txns))
    kept = 0
    shards = sorted(db.bitmaps.addressable_shards,
                    key=lambda s: s.index[1].start) if db.items.size else []
    for j, shard in enumerate(shards):
        mine_txns = txns[j * per_shard:(j + 1) * per_shard]
        want, n_kept = _host_shard(mine_txns, db.items, filter_txns, width)
        np.testing.assert_array_equal(np.asarray(shard.data), want)
        kept += n_kept
    if db.items.size:
        assert db.n_txn == (kept if filter_txns else len(txns))
    np.testing.assert_array_equal(
        bm.support_np(np.asarray(db.bitmaps)), db.supports)


@pytest.mark.parametrize("n_txn", [1, 31, 127, 129, 200, 1000])
def test_n_txn_off_the_word_grid_pads_with_zero_words(n_txn):
    txns = [[0, 1]] * n_txn
    db = build_vertical_sharded(txns, 2, 1, mesh=_mesh())
    words = np.asarray(db.bitmaps)
    # every shard is a power-of-two count of words (below the kernel's tile)
    width = words.shape[1] // 4
    assert width & (width - 1) == 0
    assert int(bm.support_np(words)[0]) == n_txn
    per_shard = _per_shard(n_txn)
    for j in range(4):
        held = max(0, min(n_txn - j * per_shard, per_shard))
        shard = words[0, j * width:(j + 1) * width]
        assert int(bm.popcount_np(shard).sum()) == held
        # the bits of a shard are its first ``held`` transactions
        bits = np.unpackbits(shard.view(np.uint8), bitorder="little")
        assert bits[:held].all() and not bits[held:].any()


def test_word_sharded_mine_moves_no_bitmap_through_the_host():
    txns = _random_txns(7, 300, 10, (0, 1, 2))
    for backend, mesh in (("tidsharded", _mesh()), ("grid", _grid())):
        res = mine(bm.to_csr(txns), 10, EclatConfig(min_sup=0.1,
                                                     backend=backend),
                   mesh=mesh)
        counts = res.stats["counts"]
        assert counts["host_bitmap_bytes"] == 0
        assert res.stats["phase_s"]["shard_build"] <= res.stats["phase_s"]["vertical"]
        assert "shard_cooc" in res.stats["phase_s"]
        # the build, level 2, and one per expand call
        assert counts["psum_calls"] == 2 + len(
            res.stats["pair_padding"]["per_level"])
    host = mine(txns, 10, EclatConfig(min_sup=0.1))
    assert host.stats["counts"]["host_bitmap_bytes"] == host.db.bitmaps.nbytes
    assert host.stats["counts"]["psum_calls"] == 0


# ---------------------------------------------------------------------------
# sharded level 2
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rows,words", [(37, 16), (64, 8), (130, 32)])
def test_sharded_cooccurrence_counts_equal_one_device_counts(rows, words):
    rng = np.random.default_rng(rows)
    bitmaps = rng.integers(0, 2**32, (rows, words), dtype=np.uint32)
    want = cooccurrence_counts(bitmaps)
    for mesh in (_mesh(), _grid()):
        placed = jax.device_put(bitmaps, NamedSharding(mesh, P(None, "data")))
        got = cooccurrence_counts(placed, mesh=mesh, axis="data")
        np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# the per-device byte cap of a call
# ---------------------------------------------------------------------------

def test_call_cap_is_a_rung_within_smem_and_the_byte_budget():
    assert eng.rung_at_most(1000, 128) == 768
    assert eng.rung_at_most(1024, 128) == 1024
    assert eng.rung_at_most(5, 128) == 128
    assert eng.call_cap(4096, 10**12, 128) == eng.MAX_PAIRS_PER_CALL
    cap = eng.call_cap(4096, 4096 * 1000, 128)
    assert cap == 768 and eng.pair_bucket(cap, 128) == cap


@pytest.mark.parametrize("backend", ["pallas", "tidsharded"])
def test_a_calls_per_device_block_stays_under_the_byte_cap(backend):
    rng = np.random.default_rng(3)
    bitmaps = rng.integers(0, 2**32, (48, 1024), dtype=np.uint32)
    left = rng.integers(0, 48, 700).astype(np.int32)
    right = rng.integers(0, 48, 700).astype(np.int32)
    sup_left = np.zeros(700, np.int32)
    mesh = _mesh() if backend == "tidsharded" else None
    kw = dict(mesh=mesh, inner="jnp") if mesh else {}
    e = eng.make_engine(backend, bucket_min=32, **kw)
    per_row = 4 * 1024 // e.word_split           # bytes of a row per device
    e.block_budget = 96 * per_row
    res = e.expand(jnp.asarray(bitmaps), left, right, sup_left,
                   mode=eng.MODE_TIDSET, min_sup=1)
    calls = e.level_padding
    assert len(calls) == 8                       # 700 pairs, 96 a call
    for _, padded in calls:
        assert padded * per_row <= e.block_budget
    whole = eng.make_engine("jnp", bucket_min=32).expand(
        jnp.asarray(bitmaps), left, right, sup_left, mode=eng.MODE_TIDSET,
        min_sup=1)
    np.testing.assert_array_equal(res.mask, whole.mask)
    np.testing.assert_array_equal(res.supports, whole.supports)
    n = whole.supports.shape[0]
    np.testing.assert_array_equal(np.asarray(res.bitmaps)[:n],
                                  np.asarray(whole.bitmaps)[:n])


_COMPILES = []
_COUNTING = []


def _on_event(event, duration, **_):
    if _COUNTING and event == "/jax/core/compile/backend_compile_duration":
        _COMPILES.append(event)


jax.monitoring.register_event_duration_secs_listener(_on_event)


@pytest.mark.parametrize("backend", ["tidsharded", "grid", "sharded"])
def test_a_second_mine_on_the_mesh_compiles_nothing(backend):
    """Each mine builds its engine anew; the mesh executors are built once
    per mesh, so a mine of a relabeled input (the same shapes) compiles
    nothing."""
    txns = _random_txns(8, 600, 10, (0, 1, 2))
    mesh = _grid() if backend == "grid" else _mesh()
    cfg = EclatConfig(min_sup=0.1, backend=backend)
    want = mine(bm.to_csr(txns), 10, cfg, mesh=mesh).support_map()
    perm = np.random.default_rng(0).permutation(len(txns))
    _COMPILES.clear()
    _COUNTING.append(True)
    try:
        got = mine(bm.to_csr([txns[i] for i in perm]), 10, cfg,
                   mesh=mesh).support_map()
    finally:
        _COUNTING.clear()
    assert got == want
    assert _COMPILES == []
