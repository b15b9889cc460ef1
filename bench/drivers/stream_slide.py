"""Back-to-back window slides through ``repro.streaming.StreamingMiner``.

Set-up draws the configuration's pool of ``pool_blocks`` blocks of
``block_txns`` baskets with its generator and builds the miner as
``launch.stream`` does (``keep_transactions=False``).  Blocks are fed in a
seeded order, a fresh permutation of the pool on every pass.  Warm-up fills
the ring with ``n_blocks`` pushes and mines that window once, which
compiles every program a slide runs, so each timed slide is over a full
window.  A unit is one ``advance(block)`` -- the push and the re-mine --
ending with the caller holding the window's full (itemset, support) map.

A seeded reservoir keeps the maps of ``compared_mines`` slides (the last
one always among them), each with the pool blocks its window holds and the
number of pushes before it.  The reference mines each kept window from the
generator's incidence arrays of those blocks, never from the program's
ring; a map stamped with another version than the pushes so far, or
another size than the full window, is of another window and is compared
with nothing.

The window is the configuration's dataset: ``n_blocks * block_txns`` is its
``n_txn``.  The traffic's ``databases`` is 1, the one pool the stream is
drawn from; it and the configuration's ``variant`` are what a batch mine of
the same data (``batch_mine.Cell``) reads.

The control and the faults take ``ENTRY``'s place (``bench/control.py``):
each is a class with the miner's constructor, ``push``, ``mine_window`` and
``advance``.
"""
from __future__ import annotations

import collections
import time

import numpy as np

from .. import control, generators, reference
from ._sample import Reservoir

UNIT = "slide"
CHIPS = (1,)                                  # one device: the ring and engine
ENTRY = ("repro.streaming", "StreamingMiner")  # what the window builds


class Cell:
    def __init__(self, config: dict, traffic: dict, seed: int):
        import repro.streaming as streaming

        win, prog = config["window"], config["program"]
        self.n_items = int(config["dataset"]["n_items"])
        self.n_blocks = int(win["n_blocks"])
        self.block_txns = int(win["block_txns"])
        self.min_sup = config["min_sup"]
        if self.n_blocks * self.block_txns != int(config["dataset"]["n_txn"]):
            raise ValueError(
                f"a window of {self.n_blocks} x {self.block_txns} baskets is "
                f"not the dataset's {config['dataset']['n_txn']}")
        n_pool = int(config["pool_blocks"])
        self.pool = generators.base_database(config, n_pool * self.block_txns)
        bounds = np.searchsorted(
            self.pool.txn, np.arange(n_pool + 1) * self.block_txns)
        self.bounds = bounds.tolist()
        txns = self.pool.transactions()
        self.blocks = [txns[b * self.block_txns:(b + 1) * self.block_txns]
                       for b in range(n_pool)]
        order_seq, sample_seq = np.random.SeedSequence(seed).spawn(2)
        self.order_rng = np.random.default_rng(order_seq)
        self.queue: collections.deque = collections.deque()
        self.fed: collections.deque = collections.deque(maxlen=self.n_blocks)
        self.pushes = 0
        # looked up here, so that a control or fault put in its place is
        # the miner the window drives
        self.miner = streaming.StreamingMiner(
            self.n_items,
            streaming.StreamConfig(min_sup=self.min_sup,
                                   n_blocks=self.n_blocks,
                                   block_txns=self.block_txns,
                                   backend=prog["backend"]),
            keep_transactions=bool(prog["keep_transactions"]))
        self.kept = Reservoir(int(traffic["compared_mines"]),
                              np.random.default_rng(sample_seq))
        self.reference = {}

    def next_block(self) -> int:
        """The pool block fed next: a fresh permutation on every pass."""
        if not self.queue:
            self.queue.extend(self.order_rng.permutation(len(self.blocks))
                              .tolist())
        b = self.queue.popleft()
        self.fed.append(b)
        self.pushes += 1
        return b

    def warm(self) -> None:
        for _ in range(self.n_blocks):
            self.miner.push(self.blocks[self.next_block()])
        self.miner.mine_window().support_map()

    def step(self, i: int, keep: bool = True) -> dict:
        """One slide: push the next block, re-mine, hold the full map."""
        block = self.blocks[self.next_block()]
        t0 = time.perf_counter()
        res = self.miner.advance(block)
        t_mined = time.perf_counter()
        answer = res.support_map()
        t1 = time.perf_counter()
        if keep:
            self.kept.offer(i, (self.pushes, tuple(self.fed), res.version,
                                res.n_txn, answer))
        stats = res.stats
        return {"t_s": t1 - t0, "answer_s": t1 - t_mined,
                "phase_s": dict(stats.get("phase_s", {})),
                "host_reads": (stats.get("counts") or {}).get("host_reads"),
                "kernel_path": stats.get("kernel_path")}

    def release(self) -> None:
        """Drop what the program holds before the reference runs."""
        self.miner = None

    def window_db(self, blocks) -> generators.Database:
        """The window of pool ``blocks``, oldest first, from the generator's
        incidence arrays."""
        txn, item = [], []
        for j, b in enumerate(blocks):
            lo, hi = self.bounds[b], self.bounds[b + 1]
            txn.append(self.pool.txn[lo:hi] + (j - b) * self.block_txns)
            item.append(self.pool.item[lo:hi])
        return generators.Database(np.concatenate(txn), np.concatenate(item),
                                   len(blocks) * self.block_txns, self.n_items)

    def want(self, blocks) -> dict:
        if blocks not in self.reference:
            self.reference[blocks] = reference.mine(self.window_db(blocks),
                                                    self.min_sup)
        return self.reference[blocks]

    def compare(self, kept) -> int:
        """Wrong itemsets of one kept map against the reference of its
        window; a map stamped for another window is compared with
        nothing."""
        pushes, blocks, version, n_txn, answer = kept
        want = self.want(blocks)
        if version != pushes or n_txn != self.n_blocks * self.block_txns:
            return len(answer) + len(want)
        return reference.compare(answer, want)

    def check(self) -> dict:
        """Compare every kept map with the reference of its window."""
        wrong = [self.compare(kept) for _, kept in self.kept.items()]
        return {"wrong_itemsets": sum(wrong), "answers_compared": len(wrong),
                "wrong_answers": sum(1 for w in wrong if w)}


def end_to_end(records: list, window_s: float) -> dict:
    """``mine_s``: the window's time over the slides it completed."""
    return {"mine_s": window_s / len(records)}


def labels(record: dict, start: int, end: int) -> list:
    """Host phases of one traced slide span, for the idle-gap split: the
    push (ring write, then the count deltas) leads the slide, level 2 and
    the bottom-up levels close the re-mine, the map is built after it."""
    ns = 1e9
    ph = record["phase_s"]
    mined = end - int(record["answer_s"] * ns)
    push_end = min(start + int(ph.get("push", 0.0) * ns), mined)
    ring_end = min(start + int(ph.get("ring", 0.0) * ns), push_end)
    cooc_start = max(push_end - int(ph.get("cooc_delta", 0.0) * ns), ring_end)
    bu_start = max(mined - int(ph.get("bottom_up", 0.0) * ns), push_end)
    l2_start = max(bu_start - int(ph.get("level2", 0.0) * ns), push_end)
    spans = [(start, ring_end, "slide:ring"),
             (ring_end, cooc_start, "slide:other"),
             (cooc_start, push_end, "slide:cooc_delta"),
             (push_end, l2_start, "slide:other"),
             (l2_start, bu_start, "slide:level2"),
             (bu_start, mined, "slide:bottom_up"),
             (mined, end, "slide:answer")]
    return [s for s in spans if s[1] > s[0]]


# --- the control and the faults, in ENTRY's place -------------------------

class Published:
    """A window's map as the miner publishes it: the map, its stats, the
    version and the live transactions it was mined from."""

    def __init__(self, answer: dict, stats: dict, version: int, n_txn: int):
        self._answer = answer
        self.stats = stats
        self.version = version
        self.n_txn = n_txn

    def support_map(self) -> dict:
        return dict(self._answer)


class ControlMiner:
    """The one-level-short reference behind the miner's interface: it keeps
    the window's blocks and mines them with ``control.depth_cut_mine``."""

    def __init__(self, n_items, config, mesh=None, keep_transactions=True):
        self.n_items = int(n_items)
        self.config = config
        self.window = collections.deque(maxlen=int(config.n_blocks))
        self.window_version = 0

    def push(self, batch) -> dict:
        self.window.append([list(t) for t in batch])
        self.window_version += 1
        return {}

    def mine_window(self) -> Published:
        t0 = time.perf_counter()
        txns = [t for block in self.window for t in block]
        answer = control.depth_cut_mine(
            control.database(txns, self.n_items), self.config.min_sup)
        return Published(answer,
                         {"phase_s": {"level2": time.perf_counter() - t0}},
                         self.window_version, len(txns))

    def advance(self, batch) -> Published:
        self.push(batch)
        return self.mine_window()


CONTROL = ControlMiner


class _Faulty:
    """The program's miner, with what a fault changes laid over it."""

    def __init__(self, inner):
        self.inner = inner

    def __getattr__(self, name):
        if name == "inner":
            raise AttributeError(name)
        return getattr(self.inner, name)

    def advance(self, batch):
        self.push(batch)
        return self.mine_window()


class AnswerAltered(_Faulty):
    """Publishes the window's map with one support changed."""

    def mine_window(self):
        res = self.inner.mine_window()
        return Published(control.altered(res.support_map()), res.stats,
                         res.version, res.n_txn)


class StaleWindow(_Faulty):
    """Publishes the previous slide's map, as it was stamped."""

    last = None

    def mine_window(self):
        res = self.inner.mine_window()
        stale, self.last = self.last, res
        return res if stale is None else stale


def fault_answer_altered(program):
    return lambda *args, **kwargs: AnswerAltered(program(*args, **kwargs))


def fault_stale_window(program):
    return lambda *args, **kwargs: StaleWindow(program(*args, **kwargs))


FAULTS = {"answer_altered": fault_answer_altered,
          "stale_window": fault_stale_window}
