"""A whole run on the CPU host, past the harness's look for a chip: the
result line's schema, and ``correct`` for the program as it stands."""
import json

import pytest

import _paths
from bench import run


@pytest.mark.parametrize("workload", _paths.WORKLOADS)
def test_mine_result_line_schema(workload):
    res = run.run_cell(_paths.BENCH, workload, 2**31 + 12345, 0.5, False,
                       peaks=run.peaks_for("TPU v5 lite"),
                       overrides=_paths.small(workload))
    line = json.loads(json.dumps(res))
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert set(line["metrics"]) == {"mine_s", "setup_s"}
    assert line["metrics"]["mine_s"]["unit"] == "s"
    assert line["metrics"]["mine_s"]["value"] > 0
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert line["checks"]["wrong_itemsets"] == {"value": 0, "limit": 0}
    assert line["checks"]["answers_compared"]["value"] >= 1


def test_same_seed_same_inputs_other_seed_other_answers():
    from bench.drivers import batch_mine
    name = "mine.T10I4D100K"
    cell, config, traffic = run.find_cell(_paths.BENCH, name)
    config["dataset"].update(_paths.small(name)["dataset"])
    a = batch_mine.Cell(config, traffic, 5)
    b = batch_mine.Cell(config, traffic, 5)
    c = batch_mine.Cell(config, traffic, 6)
    assert a.inputs == b.inputs
    assert a.inputs[0] != a.inputs[1] and a.inputs[0] != c.inputs[0]
