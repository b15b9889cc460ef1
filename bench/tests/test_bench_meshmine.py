"""The four-chip mesh mine cell at its CPU test size, on four host devices:
a window runs and its kept answers equal the reference, the faults are
caught, the CSR relabeling is the relabeling ``generators.variant`` makes,
the device reference equals ``reference.py``, and the new readers read the
program's spans and kernel, or nothing from a parent without them."""
import os

_FLAG = "--xla_force_host_platform_device_count=4"
if _FLAG not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " " + _FLAG).strip()

import types  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402

import _paths  # noqa: E402
from bench import control, generators, reference, reference_device, run  # noqa: E402
from bench.drivers import mesh_mine  # noqa: E402

NAME = "meshmine.T40I10D4M"
SPAN_READERS = ("shard_build_s", "shard_cooc_s")
TRACE_READERS = ("partial_kernel_s", "partial_roofline",
                 "device_idle_share.meshmine")


def _config():
    cell, config, traffic = run.find_cell(_paths.BENCH, NAME)
    small = _paths.small(NAME)
    config["dataset"].update(small["dataset"])
    config["min_sup"] = small["min_sup"]
    return config, traffic


@pytest.fixture(scope="module")
def unit():
    import jax
    assert len(jax.devices()) >= 4, "the cell's CPU test needs 4 host devices"
    config, traffic = _config()
    cell = mesh_mine.Cell(config, traffic, 2**31 + 4321)
    cell.warm()
    cell.records = [cell.step(i) for i in range(3)]
    return cell


def test_the_mesh_spans_four_devices_and_the_mine_is_word_sharded(unit):
    assert unit.mesh.shape["data"] == 4 == unit.n_shards
    rec = unit.records[0]
    assert rec["backend"] == "tidsharded"
    assert rec["counts"]["host_bitmap_bytes"] == 0
    assert rec["counts"]["psum_calls"] >= 3


def test_kept_answers_equal_the_reference(unit):
    checks = unit.check()
    assert checks["answers_compared"] == 3
    assert checks["wrong_itemsets"] == 0 and checks["wrong_answers"] == 0


def test_csr_variant_is_the_variant_relabeling():
    config, _ = _config()
    base = generators.base_database(config)
    (offsets, items), item_map = mesh_mine.csr_variant(
        base, *mesh_mine.csr_of(base), np.random.default_rng(5))
    ref = generators.variant(base, np.random.default_rng(5))
    np.testing.assert_array_equal(item_map, ref.item_map)
    got = mesh_mine._database((offsets, items), base.n_items)
    # the same multiset of transactions, in another order
    rows = lambda db: sorted(map(tuple, np.split(db.item, np.cumsum(
        np.bincount(db.txn, minlength=db.n_txn))[:-1])))
    assert rows(got) == rows(ref)
    want = reference.relabel(reference.mine(base, 0.05), item_map)
    assert reference.mine(got, 0.05) == want


@pytest.mark.parametrize("seed,n_txn,min_sup", [(1, 3000, 0.02), (2, 777, 0.05),
                                                 (3, 130, 0.1)])
def test_device_reference_equals_the_host_reference(seed, n_txn, min_sup):
    config, _ = _config()
    db = generators.base_database(config, n_txn)
    db = generators.variant(db, np.random.default_rng(seed))
    assert reference_device.mine(db, min_sup) == reference.mine(db, min_sup)


def test_a_window_runs_and_is_correct():
    res = run.run_cell(_paths.BENCH, NAME, 2**31 + 77, 0.5, False,
                       peaks=run.peaks_for("TPU v5 lite"),
                       overrides=_paths.small(NAME))
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"mine_s", "setup_s"}
    assert res["checks"]["answers_compared"]["value"] >= 1


@pytest.mark.parametrize("fault", sorted(mesh_mine.FAULTS))
def test_fault_is_caught(fault):
    import repro.core
    with control.substituted(mesh_mine, mesh_mine.FAULTS[fault](repro.core.mine)):
        res = run.run_cell(_paths.BENCH, NAME, 13, 0.3, False,
                           overrides=_paths.small(NAME))
    assert res["correct"] is False
    assert res["checks"]["wrong_itemsets"]["value"] > 0


@pytest.mark.parametrize("metric", SPAN_READERS)
def test_span_readers_read_the_program_spans(unit, metric):
    view = types.SimpleNamespace(records=unit.records, trace=None, cell=unit,
                                 peaks={})
    value = run.load_metric(metric).read(view)
    assert value is not None and value > 0
    phase = {"shard_build_s": "vertical", "shard_cooc_s": "tri_matrix"}[metric]
    assert all(r["phase_s"][metric[:-2]] <= r["phase_s"][phase]
               for r in unit.records)


@pytest.mark.parametrize("metric", SPAN_READERS + TRACE_READERS)
def test_readers_of_a_parent_without_them_read_nothing(unit, metric):
    bare = [dict(r, phase_s={k: v for k, v in r["phase_s"].items()
                             if not k.startswith("shard_")})
            for r in unit.records]
    trace = {"busy_s": 1.0, "window_s": 2.0, "module_s": {"other": 1.0}}
    view = types.SimpleNamespace(records=bare, trace=None, cell=unit, peaks={})
    assert run.load_metric(metric).read(view) is None
    if metric in TRACE_READERS[:2]:
        view.trace = trace
        assert run.load_metric(metric).read(view) is None


def test_trace_readers_read_the_executable_per_mine(unit):
    n = len(unit.records)
    trace = {"busy_s": 0.5, "window_s": 2.0,
             "module_s": {"fused_intersect_partial_compact": 0.25 * n}}
    view = types.SimpleNamespace(records=unit.records, trace=trace, cell=unit,
                                 peaks={"hbm_bytes_per_s": 819e9})
    assert run.load_metric("partial_kernel_s").read(view) == pytest.approx(0.25)
    share = run.load_metric("partial_roofline").read(view)
    want = 100.0 * unit.needed_bytes(0) / 4 / 819e9 / 0.25
    assert share == pytest.approx(want)
    assert run.load_metric("device_idle_share.meshmine").read(view) == \
        pytest.approx(0.75)


def test_one_chunk_is_the_generators_database():
    config, _ = _config()
    from bench import quest_chunks
    got = quest_chunks.base_database(config, 2500)
    want = generators.base_database(config, 2500)
    np.testing.assert_array_equal(got.txn, want.txn)
    np.testing.assert_array_equal(got.item, want.item)
    assert (got.n_txn, got.n_items) == (want.n_txn, want.n_items)


@pytest.mark.parametrize("workers", [1, 2])
def test_chunks_draw_one_database_whatever_the_workers(monkeypatch, workers):
    config, _ = _config()
    from bench import quest_chunks
    monkeypatch.setattr(quest_chunks, "CHUNK_TXNS", 1000)
    got = quest_chunks.base_database(config, 2500, workers=workers)
    inline = quest_chunks.base_database(config, 2500, workers=1)
    np.testing.assert_array_equal(got.txn, inline.txn)
    np.testing.assert_array_equal(got.item, inline.item)
    # sorted by transaction then item, no pair twice, every basket filled
    key = got.txn * got.n_items + got.item
    assert (np.diff(key) > 0).all()
    assert np.bincount(got.txn, minlength=2500).min() >= 1
    # the first chunk continues the pool's stream: generators.quest's own
    first = generators.base_database(config, 1000)
    head = got.txn < 1000
    np.testing.assert_array_equal(got.item[head], first.item)
