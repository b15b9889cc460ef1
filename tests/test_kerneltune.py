"""Kernel-tune suite (ISSUE 7): the raw-speed pass must not change answers.

Three layers under test:

1.  **Compacting fused kernel** — ``fused_intersect_compact_pairs`` (the real
    Pallas kernel under ``interpret=True``) must match the fused XLA oracle
    ``fused_intersect_compact_ref`` bit-for-bit across modes and the edge
    regimes the epilogue has to get right: W not a multiple of ``block_w``,
    zero survivors, all survivors, and ``n_valid < Q`` bucket padding.
2.  **Autotuner mechanics** — shape classes, candidate ladders (including the
    honest single-candidate collapse off-TPU), cost-model-seeded ordering,
    the persistent table (round-trip, corrupt-cache-as-miss), and
    ``tune_shape``/``lookup`` end to end under ``interpret=True``.
3.  **Measured dispatch** — ``DispatchPolicy`` nearest-cell choice from a
    crossover table and ``resolve_engine("auto")`` routing with safe
    fallback when no table exists.

Plus the engine-level guarantee that ties it together: ``compact=True`` (one
fused dispatch, survivors only) and ``compact=False`` (legacy mask-roundtrip
two-step) mine identical itemsets.
"""
import json
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import EclatConfig, bruteforce_fim, mine
from repro.core import engine as eng
from repro.kernels import autotune
from repro.kernels.fused_intersect import (DEFAULT_BLOCK_W, compact_epilogue,
                                           fused_intersect_compact_pairs,
                                           fused_intersect_compact_ref,
                                           round_up_lanes)

MODES = [eng.MODE_TIDSET, eng.MODE_TID_TO_DIFF, eng.MODE_DIFFSET]


@pytest.fixture
def tune_cache(tmp_path, monkeypatch):
    """Point the autotune cache at a throwaway file and drop the in-process
    table around the test, so tests neither read nor pollute the real cache."""
    path = str(tmp_path / "autotune.json")
    monkeypatch.setenv(autotune.CACHE_ENV, path)
    autotune.reset()
    yield path
    autotune.reset()


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
# 2. autotuner mechanics
# ---------------------------------------------------------------------------

def test_shape_class_buckets():
    assert autotune.shape_class(1000, 100, 0, "xla") == "q1024_w128_m0_xla"
    # every q on the same pow2 rung shares the class
    assert (autotune.shape_class(513, 100, 0, "xla")
            == autotune.shape_class(1024, 100, 0, "xla"))
    # mode and kind split classes
    assert (autotune.shape_class(1000, 100, 1, "xla")
            != autotune.shape_class(1000, 100, 0, "xla"))
    assert (autotune.shape_class(1000, 100, 0, "tpu")
            != autotune.shape_class(1000, 100, 0, "xla"))


def test_candidates_xla_collapse():
    """Off-TPU the fused path is one XLA executable with no tile knob: the
    candidate list must collapse to a single width (an honest tuner does not
    sweep a parameter the executable ignores)."""
    for w in (5, 100, 600, 4000):
        cands = autotune.block_w_candidates(w, "xla")
        assert cands == [min(DEFAULT_BLOCK_W, round_up_lanes(w))]


def test_candidates_tpu_ladder():
    assert autotune.block_w_candidates(2000, "tpu") == [128, 256, 512, 1024,
                                                        2048]
    assert autotune.block_w_candidates(100, "tpu") == [128]
    # non-pow2 padded width joins the ladder as the single-block tile
    assert 384 in autotune.block_w_candidates(300, "tpu")
    for bw in autotune.block_w_candidates(700, "tpu"):
        assert bw % 128 == 0


def test_seeded_candidates_is_ordered_permutation():
    cands = autotune.block_w_candidates(2000, "tpu")
    seeded = autotune.seeded_candidates(4096, 2000, "tpu")
    assert sorted(seeded) == cands


def test_table_roundtrip(tune_cache):
    t = autotune.AutotuneTable(tune_cache)
    t.put("q64_w128_m0_tpu", autotune.KernelConfig(block_w=256),
          measured_s=1e-4)
    t.save()
    t2 = autotune.AutotuneTable(tune_cache).load()
    cfg = t2.get("q64_w128_m0_tpu")
    assert cfg is not None and cfg.block_w == 256
    assert t2.entries["q64_w128_m0_tpu"]["source"] == "measured"


def test_corrupt_cache_is_a_miss(tune_cache):
    with open(tune_cache, "w") as f:
        f.write("{not json")
    t = autotune.AutotuneTable(tune_cache).load()
    assert t.entries == {}
    assert autotune.load_table(refresh=True).get("anything") is None


def test_lookup_miss_returns_cost_model_seed(tune_cache):
    cfg = autotune.lookup(64, 40, 0, "tpu")
    assert cfg.block_w == autotune.seeded_candidates(64, 40, "tpu")[0]


def test_tune_shape_interpret_caches_winner(tune_cache):
    rec = autotune.tune_shape(16, 8, 0, kind="interpret", reps=1)
    assert rec["kind"] == "interpret"
    assert str(rec["tuned_block_w"]) in rec["candidates"]
    assert rec["model_pick"] == int(
        autotune.seeded_candidates(16, 8, "tpu")[0])
    # the winner landed in the persistent table under the tpu-class key...
    assert os.path.exists(tune_cache)
    cfg = autotune.lookup(16, 8, 0, "tpu")
    assert cfg.block_w == rec["tuned_block_w"]
    # ...and survives a cold reload
    autotune.reset()
    assert autotune.lookup(16, 8, 0, "tpu").block_w == rec["tuned_block_w"]


# ---------------------------------------------------------------------------
# 3. measured dispatch: DispatchPolicy + resolve_engine("auto")
# ---------------------------------------------------------------------------

FAKE_CELLS = [
    {"q": 256, "w": 32, "best_single": "jnp", "best_mesh": "sharded"},
    {"q": 16384, "w": 1024, "best_single": "pallas",
     "best_mesh": "tidsharded"},
    {"q": 4096, "w": 128, "best_single": "pallas"},   # no mesh sweep ran
]


@pytest.fixture
def fake_table(tmp_path):
    path = str(tmp_path / "BENCH_kerneltune.json")
    with open(path, "w") as f:
        json.dump({"jax_backend": jax.default_backend(),
                   "crossover": FAKE_CELLS}, f)
    return path


def test_policy_nearest_cell(fake_table):
    pol = eng.DispatchPolicy.load(fake_table)
    assert pol is not None and pol.source == fake_table
    assert pol.choose(100, 16) == "jnp"            # nearest (256, 32)
    assert pol.choose(200000, 4096) == "pallas"    # nearest (16384, 1024)
    assert pol.choose(100, 16, have_mesh=True) == "sharded"
    assert pol.choose(200000, 4096, have_mesh=True) == "tidsharded"
    # cell without a mesh sweep falls back to its single-device winner
    assert pol.choose(4096, 128, have_mesh=True) == "pallas"


def test_policy_missing_corrupt_empty(tmp_path):
    assert eng.DispatchPolicy.load(str(tmp_path / "nope.json")) is None
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    assert eng.DispatchPolicy.load(str(bad)) is None
    empty = tmp_path / "empty.json"
    empty.write_text(json.dumps({"crossover": []}))
    assert eng.DispatchPolicy.load(str(empty)) is None
    # cells missing q/w/best_single are filtered -> empty -> None
    junk = tmp_path / "junk.json"
    junk.write_text(json.dumps({"crossover": [{"q": 1}]}))
    assert eng.DispatchPolicy.load(str(junk)) is None


def test_policy_skips_table_of_another_platform(tmp_path):
    """A crossover table measured on another platform, or with none
    recorded, never steers this one (the committed CPU table on a TPU)."""
    for backend in ("tpu" if jax.default_backend() != "tpu" else "cpu", None):
        path = tmp_path / f"{backend}.json"
        table = {"crossover": FAKE_CELLS}
        if backend is not None:
            table["jax_backend"] = backend
        path.write_text(json.dumps(table))
        assert eng.DispatchPolicy.load(str(path)) is None
        e = eng.resolve_engine("auto", policy_path=str(path), hints=(100, 16))
        assert e.name == "pallas" and e.dispatch["policy"] is None


def test_autotune_user_table_needs_opt_in(tmp_path, monkeypatch):
    """The per-user autotune table is read only after --autotune opts in
    (or REPRO_AUTOTUNE_CACHE names a table); by default lookup is the
    cost-model seed, whatever sits in ~/.cache."""
    monkeypatch.delenv(autotune.CACHE_ENV, raising=False)
    monkeypatch.setenv("HOME", str(tmp_path))
    monkeypatch.setattr(autotune, "_USE_USER_CACHE", False)
    user = autotune.AutotuneTable(
        os.path.expanduser(os.path.join("~", ".cache", "repro-eclat",
                                        "autotune.json")))
    seed = autotune.seeded_candidates(64, 1000, "tpu")[0]
    planted = 128 if seed != 128 else 256
    user.put(autotune.shape_class(64, 1000, 0, "tpu"),
             autotune.KernelConfig(block_w=planted))
    user.save()
    autotune.reset()
    try:
        assert autotune.table_path() is None
        assert autotune.lookup(64, 1000, 0, "tpu").block_w == seed
        eng.make_engine("jnp", autotune=True)         # --autotune opts in
        assert autotune.table_path() == user.path
        assert autotune.lookup(64, 1000, 0, "tpu").block_w == planted
    finally:
        autotune.reset()


def test_policy_env_path(fake_table, monkeypatch):
    monkeypatch.setenv(eng.KERNELTUNE_ENV, fake_table)
    pol = eng.DispatchPolicy.load()
    assert pol is not None and pol.source == fake_table


def test_resolve_auto_routes_by_hints(fake_table):
    e = eng.resolve_engine("auto", policy_path=fake_table, hints=(100, 16))
    assert e.name == "jnp"
    assert e.dispatch == {"requested": "auto", "auto": True,
                          "policy": fake_table}
    e = eng.resolve_engine("auto", policy_path=fake_table,
                           hints=(200000, 4096))
    assert e.name == "pallas"


def test_resolve_auto_mesh_overrides_shard(fake_table, host_devices):
    """Under auto the policy picks the backend; a policy choice of
    ``tidsharded`` must override the default shard="pairs" instead of
    raising the contradictory-request error."""
    from repro.dist.compat import make_mesh
    mesh = make_mesh((4,), ("data",))
    e = eng.resolve_engine("auto", mesh, policy_path=fake_table,
                           hints=(200000, 4096))
    assert e.name == "tidsharded"
    e = eng.resolve_engine("auto", mesh, policy_path=fake_table,
                           hints=(100, 16))
    assert e.name == "sharded"


def test_resolve_auto_fallbacks(tmp_path):
    # no table at the explicit path -> static default, dispatch records it
    e = eng.resolve_engine("auto", policy_path=str(tmp_path / "nope.json"),
                           hints=(100, 16))
    assert e.name == "pallas"
    assert e.dispatch["auto"] is True and e.dispatch["policy"] is None
    # table but no hints -> static default
    path = tmp_path / "t.json"
    path.write_text(json.dumps({"crossover": FAKE_CELLS}))
    e = eng.resolve_engine("auto", policy_path=str(path))
    assert e.name == "pallas"


def test_resolve_non_auto_unchanged(fake_table):
    e = eng.resolve_engine("jnp", policy_path=fake_table, hints=(100, 16))
    assert e.name == "jnp" and e.dispatch["auto"] is False
    e = eng.resolve_engine("batched")
    assert e.name == "pallas" and e.dispatch["requested"] == "batched"


# ---------------------------------------------------------------------------
# engine-level: compact vs legacy bit-identity + padding accounting
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
    maps = {}
    for compact in (True, False):
        res = mine(DB, 10, EclatConfig(min_sup=25, variant="v5", p=3,
                                       backend=backend, bucket_min=32,
                                       compact=compact))
        maps[compact] = res.support_map()
    assert maps[True] == maps[False] == ORACLE


def test_mine_explicit_block_w_and_diffsets():
    res = mine(DB, 10, EclatConfig(min_sup=25, variant="v6", p=3,
                                   use_diffsets=True, backend="pallas",
                                   bucket_min=32, block_w=256))
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
