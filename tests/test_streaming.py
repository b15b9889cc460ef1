"""Sliding-window incremental mining: ring mechanics, incremental state,
bit-exact parity with batch ``mine()`` on every backend, and the live query
service (DESIGN.md §5)."""
import numpy as np
import pytest

from repro.core import EclatConfig, mine
from repro.core.bitmap import support_np
from repro.core.triangular import cooccurrence_counts
from repro.data import stream_spec, transaction_stream
from repro.serving import ItemsetQuery, StreamQueryService
from repro.streaming import StreamConfig, StreamingMiner, WindowRing

import jax.numpy as jnp

N_ITEMS = 12


def _batches(n_batches, batch_txns, seed=0, n_items=N_ITEMS):
    """Small dense batches so multi-level itemsets appear at tiny scale."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n_batches):
        batch = []
        for _ in range(batch_txns):
            t = set(rng.choice(n_items, size=rng.integers(3, 7),
                               replace=False).tolist())
            if rng.random() < 0.5:
                t |= {0, 1, 2}
            batch.append(sorted(t))
        out.append(batch)
    return out


# ---------------------------------------------------------------------------
# WindowRing mechanics
# ---------------------------------------------------------------------------

def test_ring_geometry_validation():
    with pytest.raises(ValueError, match="multiple of 32"):
        WindowRing(N_ITEMS, n_blocks=2, block_txns=33)
    with pytest.raises(ValueError, match="at least one block"):
        WindowRing(N_ITEMS, n_blocks=0, block_txns=32)
    ring = WindowRing(N_ITEMS, n_blocks=2, block_txns=32)
    with pytest.raises(ValueError, match="exceeds block capacity"):
        ring.push([[0]] * 33)


def test_ring_fill_evict_and_order():
    ring = WindowRing(N_ITEMS, n_blocks=3, block_txns=32)
    batches = _batches(5, 20, seed=1)
    for i, b in enumerate(batches):
        new_block, old_block, n_evicted = ring.push(b)
        ring.validate()
        if i < 3:
            assert n_evicted == 0 and not old_block.any()
        else:
            assert n_evicted == 20 and old_block.any()
        assert ring.n_txn == min(i + 1, 3) * 20
    # live window = the 3 newest batches, oldest first
    expect = [list(t) for b in batches[2:] for t in b]
    assert ring.window_transactions() == expect


def test_ring_partial_batches_pad_with_zero_columns():
    ring = WindowRing(N_ITEMS, n_blocks=2, block_txns=64)
    b = _batches(1, 10, seed=2)[0]
    ring.push(b)
    assert ring.n_txn == 10
    # zero pad columns contribute no support
    assert support_np(ring.words).sum() == sum(len(set(t)) for t in b)


# ---------------------------------------------------------------------------
# incremental state: supports + co-occurrence counts stay exact across slides
# ---------------------------------------------------------------------------

def test_incremental_state_matches_recompute():
    cfg = StreamConfig(min_sup=2, n_blocks=3, block_txns=32)
    miner = StreamingMiner(N_ITEMS, cfg)
    for b in _batches(6, 24, seed=3):
        miner.push(b)
        np.testing.assert_array_equal(miner.supports,
                                      support_np(miner.ring.words))
        full_cooc = cooccurrence_counts(jnp.asarray(miner.ring.words))
        np.testing.assert_array_equal(miner.cooc, full_cooc.astype(np.int64))


# ---------------------------------------------------------------------------
# parity: windowed == batch mine() over the window, all three backends
# ---------------------------------------------------------------------------

def _mesh4():
    from repro.dist.compat import make_mesh
    return make_mesh((4,), ("data",))


@pytest.mark.parametrize("backend", ["jnp", "pallas", "sharded", "tidsharded"])
def test_windowed_matches_batch_mine(backend):
    mesh = _mesh4() if backend in ("sharded", "tidsharded") else None
    cfg = StreamConfig(min_sup=5, n_blocks=3, block_txns=32,
                       backend=backend, bucket_min=16)
    miner = StreamingMiner(N_ITEMS, cfg, mesh=mesh)
    for i, batch in enumerate(_batches(6, 28, seed=4)):
        res = miner.advance(batch)
        window = miner.window_transactions()
        batch_res = mine(window, N_ITEMS,
                         EclatConfig(min_sup=5, variant="v4", p=4,
                                     backend="jnp", bucket_min=16),
                         mesh=None)
        assert res.n_txn == len(window)
        assert res.support_map() == batch_res.support_map(), f"slide {i}"
    if backend in ("sharded", "tidsharded"):
        assert miner.engine.name == backend


def test_windowed_matches_batch_fractional_min_sup():
    """Fractional min_sup resolves against the live window txn count."""
    cfg = StreamConfig(min_sup=0.2, n_blocks=2, block_txns=32)
    miner = StreamingMiner(N_ITEMS, cfg)
    for batch in _batches(4, 20, seed=5):
        res = miner.advance(batch)
        window = miner.window_transactions()
        batch_res = mine(window, N_ITEMS, EclatConfig(min_sup=0.2))
        assert res.stats["abs_min_sup"] == batch_res.stats["abs_min_sup"]
        assert res.support_map() == batch_res.support_map()


def test_windowed_parity_on_paper_stream():
    """A real T10-shaped stream (sparse, wide universe) stays bit-exact."""
    spec = stream_spec("T10I4D100K")
    cfg = StreamConfig(min_sup=0.02, n_blocks=2, block_txns=128)
    miner = StreamingMiner(spec.n_items, cfg)
    for batch in transaction_stream("T10I4D100K", 128, 4, seed=6):
        res = miner.advance(batch)
        batch_res = mine(miner.window_transactions(), spec.n_items,
                         EclatConfig(min_sup=0.02))
        assert res.support_map() == batch_res.support_map()


def test_full_25_block_ring_without_kept_transactions_matches_batch():
    """The deployment's shape (``launch.stream``'s ``keep_transactions=False``,
    25 blocks a window) at small blocks: every slide past the fill evicts
    one block, and the window mined from the ring and the cached counts
    equals batch ``mine()`` of the 25 newest batches, kept by the test."""
    spec = stream_spec("T10I4D100K")
    cfg = StreamConfig(min_sup=0.01, n_blocks=25, block_txns=64,
                       backend="pallas")
    miner = StreamingMiner(spec.n_items, cfg, keep_transactions=False)
    batches = list(transaction_stream("T10I4D100K", 64, 27, seed=12))
    for i, batch in enumerate(batches):
        res = miner.advance(batch)
        assert res.stats["n_evicted"] == (64 if i >= 25 else 0)
        assert res.version == i + 1
        if i < 24:
            continue
        window = [t for b in batches[i - 24:i + 1] for t in b]
        assert res.n_txn == len(window) == 25 * 64
        want = mine(window, spec.n_items, EclatConfig(min_sup=0.01))
        assert res.support_map() == want.support_map(), f"slide {i}"
        assert max(len(k) for k in want.support_map()) >= 2
    with pytest.raises(RuntimeError, match="keep_transactions=False"):
        miner.window_transactions()


def test_class_crossing_bookkeeping_under_drift():
    cfg = StreamConfig(min_sup=6, n_blocks=2, block_txns=64)
    miner = StreamingMiner(20, cfg)
    rng = np.random.default_rng(7)
    entered = exited = 0
    for i in range(6):
        # regime flips halfway: items 10..19 replace items 0..9
        lo = 0 if i < 3 else 10
        batch = [sorted(set(rng.choice(range(lo, lo + 10), size=4).tolist()))
                 for _ in range(40)]
        res = miner.advance(batch)
        entered += res.stats["classes"]["n_entered"]
        exited += res.stats["classes"]["n_exited"]
    assert entered > 0 and exited > 0


@pytest.mark.parametrize("backend", ["jnp", "pallas", "grid"])
@pytest.mark.parametrize("max_k", [1, 2, 3, None])
def test_streaming_max_k_matches_batch(backend, max_k):
    """Regression: mine_window ignored max_k < 3 — level 2 was always
    expanded and recorded.  Streaming must stay bit-exact with batch mine()
    at every max_k boundary."""
    if backend == "grid":
        from repro.dist.compat import make_mesh
        import jax
        mesh = make_mesh((2, 2), ("class", "data"), devices=jax.devices()[:4])
        cfg = StreamConfig(min_sup=5, n_blocks=2, block_txns=32,
                           shard="grid", max_k=max_k, bucket_min=16)
    else:
        mesh = None
        cfg = StreamConfig(min_sup=5, n_blocks=2, block_txns=32,
                           backend=backend, max_k=max_k, bucket_min=16)
    miner = StreamingMiner(N_ITEMS, cfg, mesh=mesh)
    for batch in _batches(3, 28, seed=4):
        res = miner.advance(batch)
        batch_res = mine(miner.window_transactions(), N_ITEMS,
                         EclatConfig(min_sup=5, backend="jnp", max_k=max_k,
                                     bucket_min=16))
        assert res.support_map() == batch_res.support_map()
        if max_k is not None:
            assert len(res.counts) <= max_k


def test_streaming_max_k_validation():
    miner = StreamingMiner(N_ITEMS, StreamConfig(min_sup=5, n_blocks=2,
                                                 block_txns=32, max_k=0))
    miner.push(_batches(1, 20, seed=3)[0])
    with pytest.raises(ValueError, match="max_k"):
        miner.mine_window()


def test_per_slide_engine_stats_are_deltas():
    """stats['n_intersections'] is this slide's work, not the lifetime total
    of the miner's persistent engine."""
    cfg = StreamConfig(min_sup=5, n_blocks=2, block_txns=32)
    miner = StreamingMiner(N_ITEMS, cfg)
    per_slide = [miner.advance(b).stats["n_intersections"]
                 for b in _batches(4, 28, seed=11)]
    assert sum(per_slide) == miner.engine.n_intersections
    assert all(c > 0 for c in per_slide)


def test_push_mine_separately():
    """Mining on a cadence: push() N times, mine_window() once."""
    cfg = StreamConfig(min_sup=4, n_blocks=4, block_txns=32)
    miner = StreamingMiner(N_ITEMS, cfg)
    for batch in _batches(3, 20, seed=8):
        miner.push(batch)
    res = miner.mine_window()
    batch_res = mine(miner.window_transactions(), N_ITEMS,
                     EclatConfig(min_sup=4))
    assert res.support_map() == batch_res.support_map()


def test_empty_window_and_empty_batches():
    cfg = StreamConfig(min_sup=2, n_blocks=2, block_txns=32)
    miner = StreamingMiner(N_ITEMS, cfg)
    res = miner.mine_window()
    assert res.total == 0 and res.support_map() == {}
    res = miner.advance([])
    assert res.total == 0


# ---------------------------------------------------------------------------
# invariant checks are real exceptions (they must survive `python -O`)
# ---------------------------------------------------------------------------

def _corrupt_and_mine(miner):
    """Items 0/1/2 are all frequent but 1 and 2 never co-occur; inflating
    the cached count makes the prefilter pass a pair the engine refutes."""
    miner.push([[0, 1]] * 8 + [[0, 2]] * 8)
    miner.cooc[1, 2] = miner.cooc[2, 1] = 50
    return miner.mine_window()


def test_cached_count_disagreement_raises():
    """Regression: the level-2 cross-check was a bare ``assert`` — under
    ``python -O`` a corrupt count matrix produced silently wrong windows."""
    cfg = StreamConfig(min_sup=5, n_blocks=2, block_txns=32)
    miner = StreamingMiner(N_ITEMS, cfg)
    with pytest.raises(RuntimeError, match="co-occurrence counts disagree"):
        _corrupt_and_mine(miner)


def test_ring_validate_raises_on_divergence():
    ring = WindowRing(N_ITEMS, n_blocks=2, block_txns=32)
    ring.push(_batches(1, 20, seed=13)[0])
    ring.validate()
    ring.words[0, 0] ^= np.uint32(1)            # corrupt the host mirror
    with pytest.raises(RuntimeError, match="diverged"):
        ring.validate()
    ring.words[0, 0] ^= np.uint32(1)
    ring.block_counts[0] = -1                   # corrupt the occupancy
    with pytest.raises(RuntimeError, match="block_counts"):
        ring.validate()
    ring.block_counts[0] = 0                    # support > live txns in slot
    with pytest.raises(RuntimeError, match="live transactions"):
        ring.validate()


def test_invariants_fire_under_python_O():
    """The whole point of the fix: run the corruption scenario in a
    ``python -O`` subprocess (asserts stripped) and require the exception."""
    import subprocess
    import sys
    snippet = (
        "import numpy as np\n"
        "from repro.streaming import StreamConfig, StreamingMiner\n"
        "assert False, 'proof this build strips asserts'  # -O removes this\n"
        "miner = StreamingMiner(12, StreamConfig(min_sup=5, n_blocks=2, "
        "block_txns=32))\n"
        "miner.push([[0, 1]] * 8 + [[0, 2]] * 8)\n"
        "miner.cooc[1, 2] = miner.cooc[2, 1] = 50\n"
        "try:\n"
        "    miner.mine_window()\n"
        "except RuntimeError as e:\n"
        "    print('RAISED:', type(e).__name__)\n"
        "else:\n"
        "    raise SystemExit('invariant did NOT fire under -O')\n"
    )
    import os
    env = dict(os.environ, PYTHONPATH="src" + os.pathsep + ".")
    r = subprocess.run([sys.executable, "-O", "-c", snippet],
                       capture_output=True, text=True, env=env,
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert r.returncode == 0, r.stderr
    assert "RAISED: RuntimeError" in r.stdout


# ---------------------------------------------------------------------------
# the serving-layer query surface
# ---------------------------------------------------------------------------

def _service(seed=9):
    cfg = StreamConfig(min_sup=5, n_blocks=2, block_txns=32)
    service = StreamQueryService(StreamingMiner(N_ITEMS, cfg))
    for batch in _batches(3, 30, seed=seed):
        service.ingest(batch)
    return service


def test_topk_sorted_and_bounded():
    service = _service()
    top = service.top_k_itemsets(k=5, min_len=2)
    assert 0 < len(top) <= 5
    sups = [s for _, s in top]
    assert sups == sorted(sups, reverse=True)
    assert all(len(it) >= 2 for it, _ in top)
    # support() agrees with the snapshot
    it, s = top[0]
    assert service.support(it) == s
    assert service.support((11, 10, 9)) in (0, service.support((9, 10, 11)))


def test_rules_confidence_and_cache():
    service = _service()
    rules = service.rules(min_conf=0.6)
    smap = service.result.support_map()
    for ante, cons, conf, sup in rules:
        assert conf >= 0.6
        assert sup == smap[tuple(sorted(ante + cons))]
        assert abs(conf - sup / smap[ante]) < 1e-12
    assert service.rules(min_conf=0.6) is rules          # cached per snapshot
    service.ingest(_batches(1, 30, seed=10)[0])
    assert service.rules(min_conf=0.6) is not rules      # invalidated by slide


def test_answer_batch_packs_and_answers_all():
    service = _service()
    queries = [ItemsetQuery(qid=i, kind="topk", k=3, min_len=1 + i % 2)
               for i in range(5)]
    queries.append(ItemsetQuery(qid=99, kind="rules", min_conf=0.7, k=4))
    answers, stats = service.answer_batch(queries, n_batches=3)
    assert set(answers) == {0, 1, 2, 3, 4, 99}
    assert len(answers[99]) <= 4
    assert 0 < stats["padding_efficiency"] <= 1.0
    with pytest.raises(ValueError, match="unknown query kind"):
        service.answer_batch([ItemsetQuery(qid=1, kind="nope")], 1)


def test_answer_batch_executes_the_packing_it_reports():
    """Regression: answer_batch computed a greedy-LPT packing, answered in
    input order, and discarded the assignment — the reported
    padding_efficiency described work that never happened.  The per-slot
    counts must now match the assignment pack_queries produced."""
    from repro.serving import pack_queries
    service = _service()
    queries = [ItemsetQuery(qid=i, kind="rules" if i % 3 == 0 else "topk")
               for i in range(7)]
    answers, stats = service.answer_batch(queries, n_batches=3)
    assert set(answers) == set(range(7))
    per_slot = stats["queries_per_slot"]
    assert len(per_slot) == 3 and sum(per_slot) == len(queries)
    # the executed slot loads are exactly the ones the partitioner assigned
    assign, _ = pack_queries(queries, 3, max(len(service._itemsets), 1))
    expect = [int((assign == s).sum()) for s in range(3)]
    assert per_slot == expect
    # heterogeneous work means the pack is non-trivial (not all one slot)
    assert max(per_slot) < len(queries)


def test_windowresult_rules_passthrough():
    service = _service()
    res = service.result
    assert res.rules(0.9) == [r for r in res.rules(0.9) if r[2] >= 0.9]
