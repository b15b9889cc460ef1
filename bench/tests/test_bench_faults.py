"""The comparison fails what it must: the control (the reference one level
short) and each fault a cell can have, planted in the program's timed path,
make ``correct`` come out false.  The chip check is skipped; the rest of a
run is driven as on the chip, at a size a test run holds."""
import importlib

import pytest

import _paths
from bench import control, run

FAULT_CASES = [(w, f) for w in _paths.WORKLOADS
               for f in sorted(control.driver_of(_paths.BENCH, w).FAULTS)]


def _run(name, replacement):
    driver = control.driver_of(_paths.BENCH, name)
    with control.substituted(driver, replacement):
        return run.run_cell(_paths.BENCH, name, 11, 0.3, False,
                            overrides=_paths.small(name))


@pytest.mark.parametrize("workload", _paths.WORKLOADS)
def test_control_fails(workload):
    res = _run(workload, control.driver_of(_paths.BENCH, workload).CONTROL)
    assert res["correct"] is False
    assert res["checks"]["wrong_itemsets"]["value"] > 0
    assert res["failed"] >= 1


@pytest.mark.parametrize("workload,fault", FAULT_CASES)
def test_fault_is_caught(workload, fault):
    driver = control.driver_of(_paths.BENCH, workload)
    module_name, attr = driver.ENTRY
    program = getattr(importlib.import_module(module_name), attr)
    res = _run(workload, driver.FAULTS[fault](program))
    assert res["correct"] is False
    assert res["checks"]["wrong_itemsets"]["value"] > 0
