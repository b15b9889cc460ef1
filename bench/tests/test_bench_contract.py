"""BENCHMARK.json and the files it names: every piece a run looks up by
name exists, and the names and units keep to the allowed characters."""
import importlib
import json
import os
import re
import types

import pytest

import _paths
from bench import run

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(_paths.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"] == ["python3", "bench/run.py"]
    assert bench["paths"] == ["bench"]
    assert 1 <= bench["run_seconds"] <= 51


def test_names_units_and_files(bench):
    names = [c["name"] for c in bench["configs"]]
    names += [w["name"] for w in bench["workloads"]]
    names += [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for c in bench["configs"]:
        assert c["file"].startswith("bench/")
        with open(os.path.join(_paths.ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        assert isinstance(cfg["cpu_test"], dict)
    for w in bench["workloads"]:
        assert len(w["why"]) <= 200
        cell, config, traffic = run.find_cell(bench, w["name"])
        driver = importlib.import_module(f"bench.drivers.{traffic['driver']}")
        assert w["chips"] in (1, 4) and w["chips"] in driver.CHIPS, w["name"]
        for attr in ("UNIT", "ENTRY", "CONTROL", "FAULTS", "Cell",
                     "end_to_end", "labels"):
            assert hasattr(driver, attr), (traffic["driver"], attr)


def test_at_most_half_the_cells_take_four_chips(bench):
    four = sum(1 for w in bench["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(bench["workloads"]) // 2)


def test_end_to_end_bounds(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] == "host_clock"


def test_every_per_layer_metric_has_a_reader_that_reads_nothing_from_nothing(bench):
    empty = types.SimpleNamespace(records=[], trace=None, cell=None, peaks={})
    for m in bench["per_layer"]:
        mod = run.load_metric(m["name"])
        assert mod.read(empty) is None, m["name"]


def test_per_layer_cells_report_the_metric_they_move(bench):
    e2e = {m["name"]: set(m.get("workloads",
                                [w["name"] for w in bench["workloads"]]))
           for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert set(m["workloads"]) <= e2e[m["moves"]], m["name"]
    for w in bench["workloads"]:
        got = {m["name"] for m in run.per_layer_metrics(bench, w)}
        assert got == {m["name"] for m in bench["per_layer"]
                       if w["name"] in m["workloads"]}
        assert got, w["name"]


def test_unknown_device_kind_is_an_error():
    assert run.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(run.SetupError):
        run.peaks_for("TPU v99")


def test_unknown_workload_is_an_error(bench):
    with pytest.raises(run.SetupError):
        run.find_cell(bench, "mine.nothing")
