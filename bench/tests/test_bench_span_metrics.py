"""The readers of the program's own phase spans (``support_map_s``,
``cooc_s``, ``expand_wait_s``) on records a batch mine writes, at each
configuration's CPU test size."""
import types

import pytest

import _paths
from bench import run
from bench.drivers import batch_mine

SPAN_METRICS = ("support_map_s", "cooc_s", "expand_wait_s")


@pytest.fixture(scope="module", params=_paths.WORKLOADS)
def records(request):
    name = request.param
    cell, config, traffic = run.find_cell(_paths.BENCH, name)
    small = _paths.small(name)
    config["dataset"].update(small["dataset"])
    if "min_sup" in small:
        config["min_sup"] = small["min_sup"]
    unit = batch_mine.Cell(config, traffic, 2**31 + 99)
    unit.warm()
    return [unit.step(i) for i in range(2)]


@pytest.mark.parametrize("metric", SPAN_METRICS)
def test_reader_returns_a_positive_value(records, metric):
    view = types.SimpleNamespace(records=records, trace=None, cell=None,
                                 peaks={})
    value = run.load_metric(metric).read(view)
    assert value is not None and value > 0


def test_span_metrics_lie_inside_the_phases_that_hold_them(records):
    one = types.SimpleNamespace(trace=None, cell=None, peaks={})
    for rec in records:
        one.records = [rec]
        level2, bottom_up, cooc, wait = (
            run.load_metric(m).read(one)
            for m in ("level2_s", "bottom_up_s", "cooc_s", "expand_wait_s"))
        assert cooc <= level2
        assert wait <= level2 + bottom_up
