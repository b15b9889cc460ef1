"""Kernel-tune suite: the compacting kernel and its tile width.

Three layers under test:

1.  **Compacting fused kernel** — ``fused_intersect_compact_pairs`` (the real
    Pallas kernel under ``interpret=True``) must match the fused XLA oracle
    ``fused_intersect_compact_ref`` bit-for-bit across modes and the edge
    regimes the epilogue has to get right: W not a multiple of ``block_w``,
    zero survivors, all survivors, and ``n_valid < Q`` bucket padding.
2.  **Tile width** — candidate ladders (including the single-candidate
    collapse off-TPU), cost-model ordering, and ``resolve_block_w``: the
    width a TPU compiles is derived from the shape, pinned here at the
    deployments' row widths.
3.  **Engine level** — a mine through the one compact expand path equals
    the brute-force oracle on each single-device backend.
"""
import numpy as np
import pytest
import jax.numpy as jnp

from repro.core import EclatConfig, bruteforce_fim, mine
from repro.core import engine as eng
from repro.kernels.fused_intersect import (DEFAULT_BLOCK_W, block_w_candidates,
                                           compact_epilogue,
                                           fused_intersect_compact_pairs,
                                           fused_intersect_compact_ref,
                                           resolve_block_w, round_up_lanes,
                                           seeded_candidates)

MODES = [eng.MODE_TIDSET, eng.MODE_TID_TO_DIFF, eng.MODE_DIFFSET]


def _case(q, w, seed=0):
    rng = np.random.default_rng(seed)
    p = max(q, 2)
    bitmaps = jnp.asarray(rng.integers(0, 2 ** 32, (p, w), dtype=np.uint32))
    left = jnp.asarray(rng.integers(0, p, q).astype(np.int32))
    right = jnp.asarray(rng.integers(0, p, q).astype(np.int32))
    supl = jnp.asarray(np.full(q, w * 32, np.int32))
    return bitmaps, left, right, supl


# ---------------------------------------------------------------------------
# 1. compacting kernel parity (interpret kernel vs fused XLA oracle)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("q,w", [(7, 5), (16, 200), (33, 130)])
def test_compact_kernel_matches_oracle(mode, q, w):
    """Bit-exact across modes and W-not-a-multiple-of-block_w shapes, at a
    mid threshold (mixed survivors)."""
    bm, l, r, s = _case(q, w, seed=q * 10 + mode)
    msup = jnp.int32(w * 16)
    nv = jnp.int32(q)
    ref = fused_intersect_compact_ref(bm, l, r, s, msup, nv, mode=mode)
    ker = fused_intersect_compact_pairs(bm, l, r, s, msup, nv, mode=mode,
                                        block_w=128, interpret=True)
    for a, b in zip(ref, ker):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("regime", ["none", "all", "padded"])
def test_compact_kernel_survivor_regimes(regime):
    """Zero survivors, all survivors, and n_valid < Q (bucket-ladder pad
    pairs must never survive, however permissive the threshold)."""
    q, w = 12, 40
    bm, l, r, s = _case(q, w, seed=3)
    msup = {"none": jnp.int32(10 ** 9), "all": jnp.int32(0),
            "padded": jnp.int32(0)}[regime]
    nv = jnp.int32(5 if regime == "padded" else q)
    ref = fused_intersect_compact_ref(bm, l, r, s, msup, nv, mode=0)
    ker = fused_intersect_compact_pairs(bm, l, r, s, msup, nv, mode=0,
                                        block_w=128, interpret=True)
    for a, b in zip(ref, ker):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    n_surv = int(ref[3])
    assert n_surv == {"none": 0, "all": q, "padded": 5}[regime]


def test_compact_epilogue_semantics():
    """Survivors in ascending pair order, pad rows duplicate row 0, n_valid
    excludes the tail, and the count matches the mask."""
    inter = jnp.arange(5 * 4, dtype=jnp.uint32).reshape(5, 4)
    sup = jnp.asarray([9, 1, 9, 9, 9], jnp.int32)
    mask = jnp.asarray([1, 0, 1, 1, 1], jnp.int32)
    compact, sup2, m, n_surv = compact_epilogue(inter, sup, mask, 4)
    assert int(n_surv) == 3                      # row 4 is bucket padding
    np.testing.assert_array_equal(np.asarray(m), [1, 0, 1, 1, 0])
    got = np.asarray(compact)
    np.testing.assert_array_equal(got[:3], np.asarray(inter)[[0, 2, 3]])
    np.testing.assert_array_equal(got[3:], np.asarray(inter)[[0, 0]])
    np.testing.assert_array_equal(np.asarray(sup2), np.asarray(sup))


def test_compact_epilogue_empty():
    """Q=0 is legal for the epilogue (engines early-return before the kernel,
    but the fused oracle must not be the thing that breaks)."""
    inter = jnp.zeros((0, 4), jnp.uint32)
    z = jnp.zeros((0,), jnp.int32)
    compact, sup, m, n_surv = compact_epilogue(inter, z, z, 0)
    assert compact.shape == (0, 4) and int(n_surv) == 0


# ---------------------------------------------------------------------------
# 2. tile width: candidates, cost-model order, the derived width
# ---------------------------------------------------------------------------

def test_candidates_xla_collapse():
    """Off-TPU the fused path is one XLA executable with no tile knob: the
    candidate list must collapse to a single width (there is no width for
    the executable to ignore)."""
    for w in (5, 100, 600, 4000):
        cands = block_w_candidates(w, "xla")
        assert cands == [min(DEFAULT_BLOCK_W, round_up_lanes(w))]


def test_candidates_tpu_ladder():
    assert block_w_candidates(2000, "tpu") == [128, 256, 512, 1024, 2048]
    assert block_w_candidates(100, "tpu") == [128]
    # non-pow2 padded width joins the ladder as the single-block tile
    assert 384 in block_w_candidates(300, "tpu")
    for bw in block_w_candidates(700, "tpu"):
        assert bw % 128 == 0


def test_seeded_candidates_is_ordered_permutation():
    cands = block_w_candidates(2000, "tpu")
    seeded = seeded_candidates(4096, 2000, "tpu")
    assert sorted(seeded) == cands


def test_lookup_miss_returns_cost_model_seed():
    """With no explicit width, the width is the cost model's first pick."""
    assert (resolve_block_w(None, 64, 40, 0, "tpu")
            == seeded_candidates(64, 40, "tpu")[0])
    assert resolve_block_w(256, 64, 40, 0, "tpu") == 256


# the width a TPU compiled at the parent tree (seeded_candidates(q, w,
# "tpu")[0], computed once there), at a 4,000-basket block (125 words), a
# 100,000-transaction frontier (3,125) and the 2M-transaction scale-up
# (62,500), on the pair rungs from the smallest to the per-call cap
PARENT_BLOCK_W = {125: 128, 3125: 3200, 62500: 62592}


@pytest.mark.parametrize("q", [128, 4096, 65536])
@pytest.mark.parametrize("w", sorted(PARENT_BLOCK_W))
def test_block_w_matches_parent_choice(w, q):
    for mode in MODES:
        assert resolve_block_w(None, q, w, mode, "tpu") == PARENT_BLOCK_W[w]


# ---------------------------------------------------------------------------
# 3. engine level: the compact expand path against the oracle + accounting
# ---------------------------------------------------------------------------

def _db(seed=7, n_items=10, n_txn=150):
    rng = np.random.default_rng(seed)
    txns = []
    for _ in range(n_txn):
        t = set(rng.choice(n_items, size=rng.integers(3, 7),
                           replace=False).tolist())
        if rng.random() < 0.5:
            t |= {0, 1, 2, 3}
        txns.append(sorted(t))
    return txns


DB = _db()
ORACLE = bruteforce_fim(DB, min_sup=25)


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_mine_compact_matches_legacy(backend):
    """The in-executable compaction is each engine's one expand path: its
    mine equals the brute-force oracle."""
    res = mine(DB, 10, EclatConfig(min_sup=25, variant="v5", p=3,
                                   backend=backend, bucket_min=32))
    assert res.support_map() == ORACLE


def test_mine_explicit_block_w_and_diffsets():
    """v6 diffsets on pallas at the derived tile width."""
    res = mine(DB, 10, EclatConfig(min_sup=25, variant="v6", p=3,
                                   use_diffsets=True, backend="pallas",
                                   bucket_min=32))
    assert res.support_map() == ORACLE


def test_stats_pair_padding():
    res = mine(DB, 10, EclatConfig(min_sup=25, variant="v5", p=3,
                                   backend="pallas", bucket_min=32))
    pad = res.stats.get("pair_padding")
    assert pad is not None
    assert 0.0 < pad["efficiency"] <= 1.0
    for lvl in pad["per_level"]:
        assert lvl["pairs"] <= lvl["padded_to"]
        assert lvl["efficiency"] == lvl["pairs"] / lvl["padded_to"]


def test_snapshot_is_four_tuple():
    e = eng.make_engine("pallas", bucket_min=8)
    snap = e.snapshot()
    # four work counters, then the blocking reads and their wait seconds
    assert snap == (0, 0, 0, 0, 0, 0.0)
    stats = e.stats(since=snap)
    assert stats["n_intersections"] == 0
