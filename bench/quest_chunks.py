"""A configuration's Quest database, its transactions drawn in parallel chunks.

``generators.quest`` draws every transaction from one stream, a pick round
at a time over the whole database: at 4,000,000 transactions that is
about 70 s of set-up on one core of a TPU v5e host.  Quest's
transactions are independent draws from one pool of potentially large
itemsets (Agrawal & Srikant, VLDB'94, 2.4.3), so this module draws the
pool once, exactly as ``generators.quest`` does, and the transactions in
chunks of ``CHUNK_TXNS``, each from a stream of its own, in worker
processes.  The first chunk continues the pool's stream, so a database of
one chunk is ``generators.quest``'s, draw for draw (tested).
"""
from __future__ import annotations

import concurrent.futures
import multiprocessing
import os

import numpy as np

from .generators import MAX_PICKS, Database

__all__ = ["CHUNK_TXNS", "base_database"]

CHUNK_TXNS = 500_000      # transactions a worker draws
MAX_WORKERS = 8


def _pool(rng, n_items, avg_pattern_len, n_patterns, correlation,
          corruption_mean, corruption_sd) -> dict:
    """``generators.quest``'s pool: the patterns, their weights and their
    corruption levels, drawn in its order."""
    sizes = np.clip(rng.poisson(avg_pattern_len, n_patterns), 1, n_items)
    patterns = [rng.choice(n_items, size=int(sizes[0]), replace=False)]
    for s in sizes[1:].tolist():
        prev = patterns[-1]
        n_shared = min(int(round(rng.exponential(correlation) * s)), s,
                       prev.size)
        shared = rng.choice(prev, size=n_shared, replace=False)
        rest = np.setdiff1d(np.arange(n_items), shared)
        fresh = rng.choice(rest, size=s - n_shared, replace=False)
        patterns.append(rng.permutation(np.concatenate([shared, fresh])))
    weights = rng.exponential(1.0, n_patterns)
    weights /= weights.sum()
    corrupt = np.clip(rng.normal(corruption_mean, corruption_sd, n_patterns),
                      0.0, 1.0)
    pat_len = sizes.astype(np.int64)
    return {"n_patterns": n_patterns, "weights": weights, "corrupt": corrupt,
            "pat_len": pat_len,
            "pat_off": np.concatenate([[0], np.cumsum(pat_len)[:-1]]),
            "pat_items": np.concatenate(patterns).astype(np.int64)}


def _chunk(job):
    """``(txn, item)`` incidences of ``n_txn`` transactions drawn from the
    pool: ``generators.quest``'s transaction loop."""
    rng, pool, n_txn, n_items, avg_txn_len = job
    n_patterns, weights, corrupt = (pool["n_patterns"], pool["weights"],
                                    pool["corrupt"])
    pat_len, pat_off, pat_items = (pool["pat_len"], pool["pat_off"],
                                   pool["pat_items"])
    member = np.zeros((n_txn, n_items), bool)
    count = np.zeros(n_txn, np.int64)
    target = np.maximum(1, rng.poisson(avg_txn_len, n_txn))
    active = np.arange(n_txn)
    for _ in range(MAX_PICKS):
        if active.size == 0:
            break
        pick = rng.choice(n_patterns, size=active.size, p=weights)
        lens = pat_len[pick]
        n_drop = rng.geometric(1.0 - np.minimum(corrupt[pick], 0.999)) - 1
        txn = np.repeat(active, lens)
        first = np.repeat(np.cumsum(lens) - lens, lens)
        pos = np.arange(lens.sum()) - first
        item = pat_items[np.repeat(pat_off[pick], lens) + pos]
        new = (pos >= np.repeat(n_drop, lens)) & ~member[txn, item]
        csum = np.cumsum(new)
        rank = csum - 1 - (csum[first] - new[first])
        room = np.repeat(target[active] - count[active], lens)
        add = new & (rank < room)
        member[txn[add], item[add]] = True
        count += np.bincount(txn[add], minlength=n_txn)
        active = active[count[active] < target[active]]
    empty = np.nonzero(count == 0)[0]
    member[empty, rng.integers(n_items, size=empty.size)] = True
    txn, item = np.nonzero(member)
    return txn.astype(np.int32), item.astype(np.int32)


def base_database(config: dict, n_txn: int = 0,
                  workers: int = MAX_WORKERS) -> Database:
    """The configuration's base database (its ``dataset`` shape, its
    ``generator``), ``n_txn`` transactions (by default the dataset's), in
    chunks of ``CHUNK_TXNS`` drawn by up to ``workers`` processes; chunk
    ``c > 0`` draws from ``default_rng([generator seed, c])``."""
    data, gen = config["dataset"], config["generator"]
    if gen["kind"] != "quest":
        raise ValueError(f"unknown generator kind {gen['kind']!r}")
    n_txn = int(n_txn or data["n_txn"])
    n_items = int(data["n_items"])
    seed = int(gen["seed"])
    rng = np.random.default_rng(seed)
    pool = _pool(rng, n_items, float(data["avg_pattern_len"]),
                 int(gen["n_patterns"]), float(gen["correlation"]),
                 float(gen["corruption_mean"]), float(gen["corruption_sd"]))
    starts = list(range(0, n_txn, CHUNK_TXNS)) or [0]
    jobs = [(rng if c == 0 else np.random.default_rng([seed, c]), pool,
             min(CHUNK_TXNS, n_txn - s), n_items, float(data["avg_txn_len"]))
            for c, s in enumerate(starts)]
    workers = max(1, min(workers, len(jobs), os.cpu_count() or 1))
    if workers == 1:
        parts = [_chunk(job) for job in jobs]
    else:
        # spawned workers import numpy and this module only, never JAX,
        # which holds the chip in the parent
        with concurrent.futures.ProcessPoolExecutor(
                workers, mp_context=multiprocessing.get_context("spawn")) as ex:
            parts = list(ex.map(_chunk, jobs))
    txn = np.concatenate([t.astype(np.int64) + s
                          for (t, _), s in zip(parts, starts)])
    item = np.concatenate([i.astype(np.int64) for _, i in parts])
    return Database(txn, item, n_txn, n_items)
