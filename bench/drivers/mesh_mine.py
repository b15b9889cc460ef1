"""Back-to-back batch mines of a CSR database on a word-sharded mesh.

The batch loop of ``batch_mine`` -- one caller, each mine ending with the
caller holding the full (itemset, support) map -- for a database too large
for one chip: ``repro.core.mine`` runs the configuration's backend and
shard mode over a one-axis ``"data"`` mesh of the cell's chips, and each
device builds and keeps its own word slice of every tidset.

Inputs are CSR arrays ``(offsets, items)``, what a deployment reading a
packed file hands over.  Set-up draws the configuration's base database
once, its transactions in parallel chunks (``quest_chunks``), and relabels it into ``databases`` inputs from the seed in CSR form
(:func:`csr_variant`: item ids permuted keeping equal-support order,
transactions shuffled), so every input runs the same programs at the same
shapes; warm-up mines each input once.  A seeded reservoir keeps the
answers of ``compared_mines`` mines (the last always among them).  The
reference mines the base database once, on the device
(``reference_device``, ``reference.py``'s answer), and each input's answer
is the base answer relabeled.

The control and the faults take ``ENTRY``'s place (``bench/control.py``).
"""
from __future__ import annotations

import time

import numpy as np

from .. import (control, generators, needed_bytes, quest_chunks, reference,
               reference_device)
from ._sample import Reservoir
from .batch_mine import end_to_end, labels  # noqa: F401 -- the same loop

UNIT = "mine"
CHIPS = (4,)                       # the word-sharded mesh of one host
ENTRY = ("repro.core", "mine")     # what the window calls


def csr_of(db: generators.Database):
    """A database's transactions as CSR ``(offsets, items)`` arrays."""
    lens = np.bincount(db.txn, minlength=db.n_txn)
    return (np.concatenate([[0], np.cumsum(lens)]),
            db.item.astype(np.int32))


def csr_variant(base: generators.Database, offsets: np.ndarray,
                items: np.ndarray, rng: np.random.Generator):
    """A relabeling of ``base`` (given as CSR) in CSR form, with its item map
    (base item id -> this one's): the relabeling ``generators.variant``
    makes -- item ids permuted keeping the relative order of equal-support
    items, transactions shuffled -- without sorting the incidences."""
    support = base.supports()
    new_id = rng.permutation(base.n_items)
    order = np.lexsort((np.arange(base.n_items), support))
    ties = np.split(order, np.nonzero(np.diff(support[order]))[0] + 1)
    for group in ties:                       # each group in ascending old id
        new_id[group] = np.sort(new_id[group])
    src = rng.permutation(base.n_txn)        # position p holds base txn src[p]
    lens = np.diff(offsets)[src]
    new_offsets = np.concatenate([[0], np.cumsum(lens)])
    gather = (np.repeat(offsets[:-1][src] - new_offsets[:-1], lens)
              + np.arange(new_offsets[-1]))
    return (new_offsets, new_id.astype(np.int32)[items[gather]]), new_id


class Cell:
    def __init__(self, config: dict, traffic: dict, seed: int):
        import jax
        import repro.core as core
        from jax.sharding import Mesh

        self.core = core
        self.csr = core.CSR     # a program without array input stops here
        data = config["dataset"]
        prog = config["program"]
        self.n_items = int(data["n_items"])
        self.min_sup = config["min_sup"]
        rng = np.random.default_rng(seed)
        self.base = quest_chunks.base_database(config)
        base_csr = csr_of(self.base)
        self.inputs, self.item_maps = [], []
        for _ in range(int(traffic["databases"])):
            csr, item_map = csr_variant(self.base, *base_csr, rng)
            self.inputs.append(self.csr(*csr))
            self.item_maps.append(item_map)
        # a host that shows fewer devices (a CPU test on one) mines on them all
        devices = jax.devices()[:int(prog["mesh"]["data"])]
        self.mesh = Mesh(np.array(devices), ("data",))
        self.n_shards = len(devices)
        self.eclat_config = core.EclatConfig(
            min_sup=self.min_sup, variant=prog["variant"],
            use_diffsets=bool(prog["use_diffsets"]),
            backend=prog["backend"], shard=prog["shard"])
        self.kept = Reservoir(int(traffic["compared_mines"]), rng)
        self.base_answer = None
        self.reference = {}
        self._needed = None

    def warm(self) -> None:
        """Each input once, the last first, so that the window's first mine
        (input 0) follows another."""
        for i in range(len(self.inputs)):
            self.step(len(self.inputs) - 1 - i, keep=False)

    def step(self, i: int, keep: bool = True) -> dict:
        """One mine of input ``i % databases``, through to the full map."""
        which = i % len(self.inputs)
        t0 = time.perf_counter()
        # looked up at each call, so that a control or fault put in its
        # place is the one timed
        res = self.core.mine(self.inputs[which], self.n_items,
                             self.eclat_config, mesh=self.mesh)
        t_mined = time.perf_counter()
        answer = res.support_map()
        t1 = time.perf_counter()
        if keep:
            self.kept.offer(i, (which, answer))
        stats = res.stats
        return {"t_s": t1 - t0, "mine_s": t_mined - t0,
                "answer_s": t1 - t_mined, "input": which,
                "phase_s": dict(stats.get("phase_s", {})),
                "counts": dict(stats.get("counts", {})),
                "pair_padding": stats.get("pair_padding"),
                "backend": stats.get("backend"),
                "kernel_path": stats.get("kernel_path")}

    def release(self) -> None:
        """Drop what the program holds before the reference runs."""
        self.inputs = None

    def _base_answer(self) -> dict:
        if self.base_answer is None:
            self.base_answer = reference_device.mine(self.base, self.min_sup)
        return self.base_answer

    def want(self, which: int) -> dict:
        """The reference answer for input ``which``: the base database's,
        relabeled."""
        if which not in self.reference:
            self.reference[which] = reference.relabel(
                self._base_answer(), self.item_maps[which])
        return self.reference[which]

    def check(self) -> dict:
        """Compare every kept answer with the reference of its input."""
        wrong = [reference.compare(answer, self.want(which))
                 for _, (which, answer) in self.kept.items()]
        return {"wrong_itemsets": sum(wrong), "answers_compared": len(wrong),
                "wrong_answers": sum(1 for w in wrong if w)}

    def needed_bytes(self, which: int) -> int:
        """Bytes the intersections of one mine need, over all shards: the
        same for every input, which are relabelings of the base."""
        if self._needed is None:
            want = self._base_answer()
            frequent = np.array([k[0] for k in want if len(k) == 1], np.int64)
            rows = needed_bytes.rows_with_frequent_item(
                self.base.txn, self.base.item, frequent)
            self._needed = needed_bytes.needed_bytes(want, rows)
        return self._needed


# --- the control and the faults, in ENTRY's place -------------------------

def _database(transactions, n_items: int) -> generators.Database:
    offsets, items = transactions
    txn = np.repeat(np.arange(len(offsets) - 1), np.diff(offsets))
    return generators.Database.from_pairs(txn, np.asarray(items, np.int64),
                                          len(offsets) - 1, n_items)


def control_mine(transactions, n_items, config, mesh=None):
    """The one-level-short reference, with ``mine``'s signature."""
    t0 = time.perf_counter()
    answer = control.depth_cut_mine(_database(transactions, n_items),
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
        offsets, items = transactions
        half = len(offsets) // 2
        # the support fraction is taken over the half that was mined
        return program_mine(type(transactions)(offsets[:half],
                                               items[:offsets[half - 1]]),
                            n_items, config, mesh)
    return mine


FAULTS = {"answer_altered": fault_answer_altered,
          "half_batch": fault_half_batch}
