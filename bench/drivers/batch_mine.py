"""Back-to-back batch mines through ``repro.core.mine``.

Set-up builds the configuration's base database and relabels it into
``databases`` inputs from the seed (``generators.variant``); the window
mines them in turn, so no two consecutive mines share an input, and each
mine ends with the caller holding the full (itemset, support) map.  The
inputs are relabelings of one database, so they run the same programs at
the same shapes: warm-up mines one of them once -- the last, so that the
window's first mine (input 0) follows another -- and that compiles every
program the window runs.  A seeded reservoir keeps the answers of
``compared_mines`` mines (the last one always among them) for the
comparison with the reference, which mines the base database once: each
input's answer is the base answer relabeled.

The control and the faults take ``ENTRY``'s place (``bench/control.py``).
"""
from __future__ import annotations

import time

import numpy as np

from .. import control, generators, needed_bytes, reference
from ._sample import Reservoir

UNIT = "mine"
CHIPS = (1,)                       # one device: the single-chip engine
ENTRY = ("repro.core", "mine")     # what the window calls


class Cell:
    def __init__(self, config: dict, traffic: dict, seed: int):
        import repro.core as core

        self.core = core
        data = config["dataset"]
        prog = config["program"]
        self.n_items = int(data["n_items"])
        self.min_sup = config["min_sup"]
        rng = np.random.default_rng(seed)
        self.base = generators.base_database(config)
        self.dbs = [generators.variant(self.base, rng)
                    for _ in range(int(traffic["databases"]))]
        self.inputs = [db.transactions() for db in self.dbs]
        self.eclat_config = core.EclatConfig(
            min_sup=self.min_sup, variant=prog["variant"],
            use_diffsets=bool(prog["use_diffsets"]))
        self.kept = Reservoir(int(traffic["compared_mines"]), rng)
        self.base_answer = None
        self.reference = {}

    def warm(self) -> None:
        self.step(len(self.inputs) - 1, keep=False)

    def step(self, i: int, keep: bool = True) -> dict:
        """One mine of input ``i % databases``, through to the full map."""
        which = i % len(self.inputs)
        t0 = time.perf_counter()
        # looked up at each call, so that a control or fault put in its
        # place is the one timed
        res = self.core.mine(self.inputs[which], self.n_items,
                             self.eclat_config)
        t_mined = time.perf_counter()
        answer = res.support_map()
        t1 = time.perf_counter()
        if keep:
            self.kept.offer(i, (which, answer))
        stats = res.stats
        return {"t_s": t1 - t0, "mine_s": t_mined - t0,
                "answer_s": t1 - t_mined, "input": which,
                "phase_s": dict(stats.get("phase_s", {})),
                "pair_padding": stats.get("pair_padding"),
                "backend": stats.get("backend"),
                "kernel_path": stats.get("kernel_path")}

    def release(self) -> None:
        """Drop what the program holds before the reference runs."""
        self.inputs = None

    def want(self, which: int) -> dict:
        """The reference answer for input ``which``: the reference mines the
        base database once, and each input's answer is its relabeling."""
        if which not in self.reference:
            if self.base_answer is None:
                self.base_answer = reference.mine(self.base, self.min_sup)
            self.reference[which] = reference.relabel(
                self.base_answer, self.dbs[which].item_map)
        return self.reference[which]

    def check(self) -> dict:
        """Compare every kept answer with the reference of its input."""
        wrong = [reference.compare(answer, self.want(which))
                 for _, (which, answer) in self.kept.items()]
        return {"wrong_itemsets": sum(wrong), "answers_compared": len(wrong),
                "wrong_answers": sum(1 for w in wrong if w)}

    def needed_bytes(self, which: int) -> int:
        """Bytes the intersections of one mine of input ``which`` need."""
        want = self.want(which)
        frequent = np.array([k[0] for k in want if len(k) == 1], np.int64)
        db = self.dbs[which]
        rows = needed_bytes.rows_with_frequent_item(db.txn, db.item, frequent)
        return needed_bytes.needed_bytes(want, rows)


def end_to_end(records: list, window_s: float) -> dict:
    """``mine_s``: the window's time over the mines it completed."""
    return {"mine_s": window_s / len(records)}


def labels(record: dict, start: int, end: int) -> list:
    """Host phases of one traced mine span, for the idle-gap split: the
    vertical build leads the mine, level 2 and the bottom-up levels close
    it, the map is built after it."""
    ns = 1e9
    mined = end - int(record["answer_s"] * ns)
    ph = record["phase_s"]
    v_end = start + int(ph.get("vertical", 0.0) * ns)
    bu_start = mined - int(ph.get("bottom_up", 0.0) * ns)
    l2_start = bu_start - int(ph.get("tri_matrix", 0.0) * ns)
    spans = [(start, v_end, "mine:vertical"), (v_end, l2_start, "mine:other"),
             (l2_start, bu_start, "mine:level2"),
             (bu_start, mined, "mine:bottom_up"), (mined, end, "mine:answer")]
    return [s for s in spans if s[1] > s[0]]


# --- the control and the faults, in ENTRY's place -------------------------

def control_mine(transactions, n_items, config, mesh=None):
    """The one-level-short reference, with ``mine``'s signature."""
    t0 = time.perf_counter()
    answer = control.depth_cut_mine(control.database(transactions, n_items),
                                    config.min_sup)
    return control.Result(answer,
                          {"phase_s": {"vertical": time.perf_counter() - t0}})


CONTROL = control_mine


def fault_answer_altered(program_mine):
    def mine(transactions, n_items, config, mesh=None):
        res = program_mine(transactions, n_items, config, mesh)
        return control.Result(control.altered(res.support_map()), res.stats)
    return mine


def fault_half_batch(program_mine):
    def mine(transactions, n_items, config, mesh=None):
        half = list(transactions[: len(transactions) // 2])
        # the support fraction is taken over the half that was mined
        return program_mine(half, n_items, config, mesh)
    return mine


FAULTS = {"answer_altered": fault_answer_altered,
          "half_batch": fault_half_batch}
