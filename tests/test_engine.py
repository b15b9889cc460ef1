"""Engine backend parity: every backend x mode x shape must be bit-exact.

The jnp reference defines the semantics; the fused pallas path (both the
dispatching jit and the real kernel under ``interpret=True``) and the sharded
shard_map path must reproduce its survivor masks, supports, and bitmaps
bit-for-bit — including empty, singleton, and non-multiple-of-block shapes.
Full ``mine()`` runs must agree across backends for every variant v1-v6.
"""
import numpy as np
import pytest
import jax.numpy as jnp

from repro.core import EclatConfig, bruteforce_fim, mine
from repro.core import engine as eng
from repro.core.bitmap import popcount_np

RNG = np.random.default_rng(42)

MODES = [eng.MODE_TIDSET, eng.MODE_TID_TO_DIFF, eng.MODE_DIFFSET]


def _mesh4():
    from repro.dist.compat import make_mesh
    return make_mesh((4,), ("data",))


def _grid22():
    import jax
    from repro.dist.compat import make_mesh
    return make_mesh((2, 2), ("class", "data"), devices=jax.devices()[:4])


def _engine(backend):
    if backend == "jnp":
        return eng.make_engine("jnp", bucket_min=8)
    if backend == "pallas":
        return eng.make_engine("pallas", bucket_min=8)
    if backend == "pallas-kernel":
        return eng.make_engine("pallas", bucket_min=8, interpret=True)
    if backend == "sharded-jnp":
        return eng.make_engine("sharded", mesh=_mesh4(), bucket_min=8, inner="jnp")
    if backend == "sharded-pallas-kernel":
        return eng.make_engine("sharded", mesh=_mesh4(), bucket_min=8,
                               inner="pallas", interpret=True)
    if backend == "tidsharded-jnp":
        return eng.make_engine("tidsharded", mesh=_mesh4(), bucket_min=8,
                               inner="jnp")
    if backend == "tidsharded-pallas-kernel":
        return eng.make_engine("tidsharded", mesh=_mesh4(), bucket_min=8,
                               inner="pallas", interpret=True)
    if backend == "grid-jnp":
        return eng.make_engine("grid", mesh=_grid22(), bucket_min=8,
                               inner="jnp")
    if backend == "grid-pallas-kernel":
        return eng.make_engine("grid", mesh=_grid22(), bucket_min=8,
                               inner="pallas", interpret=True)
    raise AssertionError(backend)


def _check_level(res, ref_bm, ref_sup, ref_mask, w):
    """Shared parity assertions.  The tid-sharded backend zero-pads the word
    axis to a shard multiple, so bitmap comparison is on [:, :w] plus an
    all-zero check on any pad columns."""
    np.testing.assert_array_equal(res.mask, ref_mask)
    np.testing.assert_array_equal(res.supports, ref_sup)
    # survivors live in rows [:S]; rows beyond are rung padding
    assert res.bitmaps.shape[0] >= ref_bm.shape[0]
    got = np.asarray(res.bitmaps)[: ref_bm.shape[0]]
    np.testing.assert_array_equal(got[:, :w], ref_bm)
    assert not got[:, w:].any()


def _oracle(bitmaps, left, right, sup_left, mode, min_sup):
    a = bitmaps[left]
    b = bitmaps[right]
    if mode == eng.MODE_TIDSET:
        inter = a & b
        sup = popcount_np(inter).sum(-1)
    elif mode == eng.MODE_TID_TO_DIFF:
        inter = a & ~b
        sup = sup_left - popcount_np(inter).sum(-1)
    else:
        inter = b & ~a
        sup = sup_left - popcount_np(inter).sum(-1)
    mask = sup >= min_sup
    return inter[mask], sup[mask], mask


def _case(p, w, q, seed):
    rng = np.random.default_rng(seed)
    bitmaps = rng.integers(0, 2**32, (p, w), dtype=np.uint32)
    left = rng.integers(0, p, q).astype(np.int32)
    right = rng.integers(0, p, q).astype(np.int32)
    sup_left = popcount_np(bitmaps[left]).sum(-1).astype(np.int32) if q else np.zeros(0, np.int32)
    dev = rng.integers(0, 4, q).astype(np.int64)
    return bitmaps, left, right, sup_left, dev


# interpret-mode pallas is slow; keep its shapes small but still cover the
# empty / singleton / non-multiple-of-block corners
SHAPES_FAST = [(1, 1, 0), (1, 1, 1), (5, 3, 13), (64, 4, 37), (130, 9, 21)]
SHAPES_INTERP = [(1, 1, 0), (1, 1, 1), (5, 3, 13), (9, 5, 7)]


@pytest.mark.parametrize("backend", ["jnp", "pallas", "sharded-jnp",
                                     "tidsharded-jnp", "grid-jnp"])
@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("p,w,q", SHAPES_FAST)
def test_backend_parity(backend, mode, p, w, q):
    bitmaps, left, right, sup_left, dev = _case(p, w, q, seed=p * 1000 + w * 10 + q)
    min_sup = max(1, int(0.4 * w * 32))
    ref_bm, ref_sup, ref_mask = _oracle(bitmaps, left, right, sup_left, mode, min_sup)
    e = _engine(backend)
    res = e.expand(jnp.asarray(bitmaps), left, right, sup_left,
                   mode=mode, min_sup=min_sup,
                   device_of_pair=dev % max(e.n_devices, 1))
    _check_level(res, ref_bm, ref_sup, ref_mask, w)


@pytest.mark.parametrize("backend", ["pallas-kernel", "sharded-pallas-kernel",
                                     "tidsharded-pallas-kernel",
                                     "grid-pallas-kernel"])
@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("p,w,q", SHAPES_INTERP)
def test_pallas_kernel_parity(backend, mode, p, w, q):
    """The real Pallas kernel (interpret=True on this CPU host) is bit-exact."""
    bitmaps, left, right, sup_left, dev = _case(p, w, q, seed=p * 77 + w * 5 + q)
    min_sup = max(1, int(0.4 * w * 32))
    ref_bm, ref_sup, ref_mask = _oracle(bitmaps, left, right, sup_left, mode, min_sup)
    e = _engine(backend)
    res = e.expand(jnp.asarray(bitmaps), left, right, sup_left,
                   mode=mode, min_sup=min_sup,
                   device_of_pair=dev % max(e.n_devices, 1))
    _check_level(res, ref_bm, ref_sup, ref_mask, w)


def test_sharded_rejects_out_of_range_device_ids():
    """Regression: an out-of-range device id used to leave slot_of_pair
    uninitialized (np.empty garbage) and return wrong supports silently."""
    bitmaps, left, right, sup_left, _ = _case(16, 4, 9, seed=3)
    e = _engine("sharded-jnp")  # 4-device mesh
    for bad in (np.full(9, 4, np.int64),                   # == n_devices
                np.array([0, 1, 2, 3, 0, 1, 2, 3, 17]),    # far out
                np.array([0, -1, 0, 0, 0, 0, 0, 0, 0])):   # negative
        with pytest.raises(ValueError, match="device_of_pair"):
            e.expand(jnp.asarray(bitmaps), left, right, sup_left,
                     mode=eng.MODE_TIDSET, min_sup=1,
                     device_of_pair=bad)
    with pytest.raises(ValueError, match="device_of_pair"):
        e.expand(jnp.asarray(bitmaps), left, right, sup_left,
                 mode=eng.MODE_TIDSET, min_sup=1,
                 device_of_pair=np.zeros(5, np.int64))      # wrong shape


def test_kernel_multi_word_blocks():
    """W spanning several word blocks exercises the popcount accumulator."""
    from repro.kernels.fused_intersect import (fused_intersect_pairs,
                                               fused_intersect_ref)
    bitmaps, left, right, sup_left, _ = _case(12, 300, 6, seed=5)
    bm = jnp.asarray(bitmaps)
    l, r, s = jnp.asarray(left), jnp.asarray(right), jnp.asarray(sup_left)
    for mode in MODES:
        ri, rs, rm = fused_intersect_ref(bm, l, r, s, 900, mode=mode)
        ki, ks, km = fused_intersect_pairs(bm, l, r, s, 900, mode=mode,
                                           block_w=128, interpret=True)
        np.testing.assert_array_equal(np.asarray(ri), np.asarray(ki))
        np.testing.assert_array_equal(np.asarray(rs), np.asarray(ks))
        np.testing.assert_array_equal(np.asarray(rm), np.asarray(km))


# ---------------------------------------------------------------------------
# full mine() parity across backends
# ---------------------------------------------------------------------------

def _db(seed=7, n_items=10, n_txn=150):
    rng = np.random.default_rng(seed)
    txns = []
    for _ in range(n_txn):
        t = set(rng.choice(n_items, size=rng.integers(3, 7), replace=False).tolist())
        if rng.random() < 0.5:
            t |= {0, 1, 2, 3}
        txns.append(sorted(t))
    return txns


DB = _db()
ORACLE = bruteforce_fim(DB, min_sup=25)


@pytest.mark.parametrize("variant", ["v1", "v2", "v3", "v4", "v5", "v6"])
def test_mine_backend_parity(variant):
    maps = {}
    for backend in ("jnp", "pallas"):
        res = mine(DB, 10, EclatConfig(min_sup=25, variant=variant, p=3,
                                       use_diffsets=(variant == "v6"),
                                       backend=backend, bucket_min=32))
        assert res.stats["backend"] == backend
        maps[backend] = res.support_map()
    assert maps["jnp"] == maps["pallas"] == ORACLE


def test_mine_no_trimatrix_backend_parity():
    r_jnp = mine(DB, 10, EclatConfig(min_sup=25, variant="v5", p=3,
                                     tri_matrix=False, backend="jnp"))
    r_pal = mine(DB, 10, EclatConfig(min_sup=25, variant="v5", p=3,
                                     tri_matrix=False, backend="pallas"))
    assert r_jnp.support_map() == r_pal.support_map() == ORACLE


# ---------------------------------------------------------------------------
# per-call pair cap (kernels.fused_intersect.MAX_PAIRS_PER_CALL)
# ---------------------------------------------------------------------------

SMALL_CAP = 16


@pytest.mark.parametrize("backend", ["jnp", "pallas", "pallas-kernel",
                                     "sharded-jnp", "tidsharded-jnp",
                                     "grid-jnp"])
@pytest.mark.parametrize("mode", MODES)
def test_level_above_pair_cap_is_split(backend, mode, monkeypatch):
    """A level with more pairs than the cap runs as calls of at most the
    cap, and the concatenated survivors are bit-exact with the oracle."""
    monkeypatch.setattr(eng, "MAX_PAIRS_PER_CALL", SMALL_CAP)
    p, w, q = 40, 3, 3 * SMALL_CAP + 5
    bitmaps, left, right, sup_left, dev = _case(p, w, q, seed=7 + mode)
    _, all_sup, _ = _oracle(bitmaps, left, right, sup_left, mode, 0)
    min_sup = int(np.median(all_sup))
    ref_bm, ref_sup, ref_mask = _oracle(bitmaps, left, right, sup_left, mode,
                                        min_sup)
    assert 0 < ref_mask.sum() < q
    e = _engine(backend)
    res = e.expand(jnp.asarray(bitmaps), left, right, sup_left,
                   mode=mode, min_sup=min_sup,
                   device_of_pair=dev % max(e.n_devices, 1))
    _check_level(res, ref_bm, ref_sup, ref_mask, w)
    per_call = [padded // max(e.n_devices, 1) for _, padded in e.level_padding]
    assert len(per_call) == 4 and max(per_call) <= SMALL_CAP


def test_pair_rung_never_exceeds_cap():
    cap = eng.MAX_PAIRS_PER_CALL
    assert eng.pair_bucket(cap, 128) == cap
    assert eng.pair_bucket(cap - 1, 96) == cap     # 96 x 2**k skips the cap
    assert eng.pair_bucket(1, 4 * cap) == cap      # a floor above the cap
    assert max(eng.pair_bucket(n, 128) for n in (1, 129, cap // 2 + 1)) <= cap


@pytest.mark.parametrize("backend", ["jnp", "pallas", "tidsharded", "grid"])
def test_mine_no_trimatrix_above_pair_cap(backend, monkeypatch):
    """The all-pairs level-2 path (tri-matrix off) with more pairs than the
    cap is split into capped calls and stays bit-exact with jnp and the
    oracle."""
    monkeypatch.setattr(eng, "MAX_PAIRS_PER_CALL", SMALL_CAP)
    mesh = {"tidsharded": _mesh4, "grid": _grid22}.get(backend)
    res = mine(DB, 10, EclatConfig(min_sup=25, variant="v4", p=3,
                                   tri_matrix=False, backend=backend),
               mesh=mesh() if mesh else None)
    n1 = res.stats["n_freq_items"]
    n2 = n1 * (n1 - 1) // 2
    assert n2 > SMALL_CAP
    calls = res.stats["pair_padding"]["per_level"]
    split = [SMALL_CAP] * (n2 // SMALL_CAP) + [n2 % SMALL_CAP] * bool(n2 % SMALL_CAP)
    assert [c["pairs"] for c in calls[:len(split)]] == split
    width = res.stats.get("n_class_shards", 1)
    assert all(c["padded_to"] // width <= SMALL_CAP for c in calls)
    assert res.support_map() == ORACLE


def test_mine_mesh_routes_to_sharded():
    res = mine(DB, 10, EclatConfig(min_sup=25, variant="v4", p=4), mesh=_mesh4())
    assert res.stats["backend"] == "sharded"
    assert res.support_map() == ORACLE
    assert "device_balance" in res.stats


@pytest.mark.parametrize("entry", ["mine", "StreamingMiner"])
@pytest.mark.parametrize("name", ["auto", "batched"])
def test_removed_backend_names_raise(name, entry):
    """The measured-dispatch name and its alias are gone: naming one is the
    unknown-backend error, listing the backends there are."""
    with pytest.raises(ValueError, match="unknown engine backend") as err:
        if entry == "mine":
            mine(DB, 10, EclatConfig(min_sup=25, backend=name))
        else:
            from repro.streaming import StreamConfig, StreamingMiner
            StreamingMiner(10, StreamConfig(min_sup=25, n_blocks=2,
                                            block_txns=64, backend=name))
    assert str(eng.available_backends()) in str(err.value)


# ---------------------------------------------------------------------------
# registry + bucket ladder
# ---------------------------------------------------------------------------

def test_registry_surface():
    assert set(eng.available_backends()) >= {"jnp", "pallas", "sharded",
                                             "tidsharded", "grid"}
    with pytest.raises(ValueError, match="unknown engine backend"):
        eng.make_engine("nope")
    for meshful in ("sharded", "tidsharded", "grid"):
        with pytest.raises(ValueError, match="requires a mesh"):
            eng.make_engine(meshful)


def test_pair_buffers_ladder_reuse():
    bufs = eng.PairBuffers(floor=8)
    qb1, l1, _, _ = bufs.fill(np.arange(5, dtype=np.int32),
                              np.arange(5, dtype=np.int32),
                              np.arange(5, dtype=np.int32))
    assert qb1 == 8 and l1.shape == (8,) and (l1[5:] == 0).all()
    # stale tail from a previous, larger fill must be rezeroed
    qb2, l2, _, _ = bufs.fill(np.full(3, 7, np.int32),
                              np.full(3, 7, np.int32),
                              np.full(3, 7, np.int32))
    assert qb2 == 8 and l2 is l1 and (l2[3:] == 0).all()
    qb3, l3, _, _ = bufs.fill(np.zeros(20, np.int32),
                              np.zeros(20, np.int32),
                              np.zeros(20, np.int32))
    assert qb3 == 24 and l3.shape == (24,) and l3 is not l1


def test_bucket_size_ladder():
    # half-pow2 ladder: floor * {1, 1.5, 2, 3, 4, 6, 8, ...}
    assert [eng.bucket_size(n, 8) for n in (0, 1, 8, 9, 12, 13, 100)] \
        == [8, 8, 8, 12, 12, 16, 128]
    assert [eng.bucket_size(n, 1024) for n in (1, 1025, 1537, 3073)] \
        == [1024, 1536, 2048, 4096]
    # every pair rung (floor f) is also a rung of the finer survivor ladder
    # (floor f/8), so fused-epilogue compaction slices never exceed the block
    for n in (1, 7, 9, 100, 1000, 5000):
        qb = eng.bucket_size(n, 1024)
        assert eng.bucket_size(n, 128) <= qb
