"""The slide cell's driver at its configuration's CPU test size: the seeded
block order, the per-window reference, the version stamp check, the span
and counter readers, and the idle-gap labels."""
import types

import pytest

import _paths
from bench import control, reference, run
from bench.drivers import stream_slide

NAME = "slide.T10I4D100K"
SPAN_METRICS = ("push_s", "cooc_delta_s", "slide_level2_s",
                "slide_bottom_up_s", "slide_support_map_s")


def _cell(seed):
    _, config, traffic = run.find_cell(_paths.BENCH, NAME)
    for key, upd in _paths.small(NAME).items():
        if isinstance(config.get(key), dict):
            config[key].update(upd)
        else:
            config[key] = upd
    return stream_slide.Cell(config, traffic, seed)


@pytest.fixture(scope="module")
def slid():
    """A warmed cell and the records of six slides."""
    cell = _cell(2**31 + 5)
    cell.warm()
    return cell, [cell.step(i) for i in range(6)]


def _order(cell, n):
    return [cell.next_block() for _ in range(n)]


def test_same_seed_same_order_other_seed_other_order():
    a, b, c = _cell(7), _cell(7), _cell(8)
    n_pool = len(a.blocks)
    order = _order(a, 3 * n_pool)
    assert order == _order(b, 3 * n_pool)
    assert order != _order(c, 3 * n_pool)
    # every pass is a fresh permutation of the pool
    passes = [order[i:i + n_pool] for i in range(0, 3 * n_pool, n_pool)]
    assert all(sorted(p) == list(range(n_pool)) for p in passes)
    assert passes[0] != passes[1]


def test_kept_windows_are_full_and_their_reference_is_mined_from_their_blocks(
        slid):
    cell, _ = slid
    kept = cell.kept.items()
    assert len(kept) == 6
    for i, (pushes, blocks, version, n_txn, answer) in kept:
        assert pushes == version == cell.n_blocks + i + 1
        assert n_txn == cell.n_blocks * cell.block_txns
        assert len(blocks) == cell.n_blocks
        fed = [t for b in blocks for t in cell.blocks[b]]
        assert cell.window_db(blocks).transactions() == fed
        want = reference.mine(control.database(fed, cell.n_items),
                              cell.min_sup)
        assert cell.want(blocks) == want
        assert max(len(k) for k in want) >= 2
        assert answer == want
    assert cell.check() == {"wrong_itemsets": 0, "answers_compared": 6,
                            "wrong_answers": 0}


def test_a_map_stamped_for_another_window_is_compared_with_nothing(slid):
    cell, _ = slid
    _, (pushes, blocks, version, n_txn, answer) = cell.kept.items()[-1]
    assert cell.compare((pushes, blocks, version, n_txn, answer)) == 0
    want = len(answer) + len(cell.want(blocks))
    assert cell.compare((pushes, blocks, version - 1, n_txn, answer)) == want
    assert cell.compare((pushes, blocks, version, n_txn - 1, answer)) == want


@pytest.mark.parametrize("metric", SPAN_METRICS + ("host_reads_per_slide",))
def test_reader_returns_a_positive_value(slid, metric):
    _, records = slid
    view = types.SimpleNamespace(records=records, trace=None, cell=None,
                                 peaks={})
    value = run.load_metric(metric).read(view)
    assert value is not None and value > 0


def test_push_parts_lie_inside_the_push_and_reads_count_both_passes(slid):
    from repro.core.triangular import cooc_blocks
    cell, records = slid
    for rec in records:
        ph = rec["phase_s"]
        assert ph["ring"] + ph["cooc_delta"] <= ph["push"] <= rec["t_s"]
        assert rec["host_reads"] > 2 * cooc_blocks(cell.n_items)


def test_readers_of_a_parent_without_the_spans_read_nothing():
    old = [{"phase_s": {"level2": 0.1, "bottom_up": 0.1}, "host_reads": None}]
    view = types.SimpleNamespace(records=old, trace=None, cell=None, peaks={})
    for metric in ("push_s", "cooc_delta_s", "slide_support_map_s",
                   "host_reads_per_slide", "cooc_device_s",
                   "device_idle_share.slide"):
        assert run.load_metric(metric).read(view) is None, metric


def test_trace_readers_read_cooc_time_per_slide_and_the_idle_share(slid):
    _, records = slid
    trace = {"module_s": {"_cooc_block": 0.6}, "busy_s": 1.0,
             "window_s": 4.0}
    view = types.SimpleNamespace(records=records, trace=trace, cell=None,
                                 peaks={})
    assert run.load_metric("cooc_device_s").read(view) == pytest.approx(0.1)
    assert run.load_metric("device_idle_share.slide").read(view) == 0.75


def test_labels_tile_the_slide_in_order(slid):
    _, records = slid
    for rec in records:
        start = 1_000_000
        end = start + int(rec["t_s"] * 1e9)
        spans = stream_slide.labels(rec, start, end)
        assert spans[0][0] == start and spans[-1][1] == end
        assert all(a[1] == b[0] for a, b in zip(spans, spans[1:]))
        assert {"slide:ring", "slide:cooc_delta", "slide:level2",
                "slide:answer"} <= {s[2] for s in spans}
