#!/usr/bin/env python3
"""The control: the reference put in the program's place, one level short.

    python3 bench/control.py --workload mine.T10I4D100K --seeds 11,12,13 --seconds 10

Each configuration states its guarantee: every itemset at or above the
threshold, with its exact support.  The control breaks its completeness the
way a change that bounds the search depth would -- it mines with the
reference and stops one level before the deepest frequent level -- and
runs through the harness exactly as the program does, timed window and
comparison included.  Every run of it has to come out not correct; its
``wrong_itemsets`` readings are the upper readings the limit is set below.

A cell's driver names the program entry its window calls (``ENTRY``), the
control that takes its place (``CONTROL``) and the faults that can be
planted there (``FAULTS``); this module holds what they share.  The tests
drive the faults at a small size.
"""
from __future__ import annotations

import argparse
import contextlib
import importlib
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import generators, reference  # noqa: E402


def database(transactions, n_items: int) -> generators.Database:
    """Item-id lists, as the program receives them, as a database."""
    lens = [len(t) for t in transactions]
    txn = [i for i, n in enumerate(lens) for _ in range(n)]
    item = [x for t in transactions for x in t]
    return generators.Database.from_pairs(txn, item, len(transactions),
                                          n_items)


def depth_cut_mine(db: generators.Database, min_sup) -> dict:
    """The reference answer without its deepest level."""
    full = reference.mine(db, min_sup)
    deepest = max(len(k) for k in full)
    return {k: v for k, v in full.items() if len(k) < deepest}


def altered(answer: dict) -> dict:
    """One support changed, where the answer is produced."""
    out = dict(answer)
    key = max(out, key=lambda k: (len(k), k))
    out[key] += 1
    return out


class Result:
    """Stands in for the program's result objects."""

    def __init__(self, answer: dict, stats: dict):
        self._answer = answer
        self.stats = stats

    def support_map(self) -> dict:
        return dict(self._answer)


def driver_of(bench: dict, workload: str):
    from bench import run

    _, _, traffic = run.find_cell(bench, workload)
    return importlib.import_module(f"bench.drivers.{traffic['driver']}")


@contextlib.contextmanager
def substituted(driver, replacement):
    """Put ``replacement`` where ``driver``'s window calls the program."""
    module_name, attr = driver.ENTRY
    module = importlib.import_module(module_name)
    saved = getattr(module, attr)
    setattr(module, attr, replacement)
    try:
        yield
    finally:
        setattr(module, attr, saved)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, one control run each")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    from bench import run

    sys.path.insert(0, os.path.join(ROOT, "src"))
    bench = run.load_json("BENCHMARK.json")
    driver = driver_of(bench, args.workload)
    readings = []
    with substituted(driver, driver.CONTROL):
        for seed in [int(s) for s in args.seeds.split(",")]:
            res = run.run_cell(bench, args.workload, seed, args.seconds,
                               False, peaks={})
            readings.append({"seed": seed, "correct": res["correct"],
                             "attempted": res["attempted"],
                             **{k: v["value"] for k, v in res["checks"].items()}})
            print(json.dumps(readings[-1]), flush=True)
    print(json.dumps({"workload": args.workload, "control": "depth_cut",
                      "all_not_correct": not any(r["correct"] for r in readings),
                      "min_wrong_itemsets": min(r["wrong_itemsets"]
                                                for r in readings)}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
