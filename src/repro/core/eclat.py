"""RDD-Eclat on JAX: the paper's five variants (plus a beyond-paper sixth).

Execution model (see DESIGN.md §2-3): the host process plays the Spark driver
— it owns data-dependent control flow (class segmentation, survivor
bookkeeping, checkpointing) — while devices execute the tidset-intersection
hot loop behind the ``core.engine`` backend interface (jnp reference, fused
Pallas kernel, or shard_map over a mesh).  Equivalence classes are assigned
to partitions once, from their 1-length prefix, and descendants never
migrate: the mining is communication-free after partitioning, exactly the
property the paper engineers on Spark.

This module contains no device-execution details — no pallas, shard_map or
padding logic; ``EclatConfig.backend`` selects the engine backend.

Variants:
  v1  vertical build via scatter, no filtering, default partitioner
  v2  + filtered transactions (dropped before the pack)
  v3  + accumulator-built vertical DB (psum path)
  v4  v3 + hash partitioner (p user-set)
  v5  v3 + reverse-hash partitioner
  v6  (beyond paper) v3 + greedy-LPT partitioner, optional dEclat diffsets
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, List, Optional, Sequence, Tuple, Union

import numpy as np
import jax
import jax.numpy as jnp

from . import bitmap as bm
from . import engine as eng
from .engine import merge_stats
from .equivalence import class_segments, pair_work, segment_pairs
from .itemsets import ItemsetStore, LevelRecord
from .partitioners import assign_partitions, partition_stats
from .triangular import cooc_blocks, cooccurrence_counts, frequent_pairs
from .vertical import VerticalDB, build_vertical, build_vertical_sharded
from ..spans import span

# the batch miner's phases, as ``mine.<phase>`` spans and ``phase_s`` keys
_span = functools.partial(span, prefix="mine.")

__all__ = ["EclatConfig", "EclatResult", "mine", "resume_mine",
           "resolve_min_sup", "run_bottom_up", "VARIANTS"]

VARIANTS: Dict[str, dict] = {
    "v1": dict(filter_txns=False, accumulator=False, partitioner="default"),
    "v2": dict(filter_txns=True, accumulator=False, partitioner="default"),
    "v3": dict(filter_txns=True, accumulator=True, partitioner="default"),
    "v4": dict(filter_txns=True, accumulator=True, partitioner="hash"),
    "v5": dict(filter_txns=True, accumulator=True, partitioner="reverse_hash"),
    "v6": dict(filter_txns=True, accumulator=True, partitioner="greedy"),
}


def resolve_min_sup(min_sup, n_txn: int) -> int:
    """Support threshold -> absolute count, disambiguated by *type*:

    - a float in (0, 1] is a support **fraction** of ``n_txn`` (so
      ``min_sup=1.0`` means "appears in every transaction", resolving to
      ``n_txn`` — not the absolute count 1 a value-based cutoff would read);
    - an int >= 1 (or a float > 1) is an absolute **count**.

    Anything else (zero, negatives, bools) is rejected.  Shared by the batch
    and streaming configs: the streaming/batch bit-exactness contract
    (DESIGN.md §5) requires both to resolve a threshold identically.
    """
    if isinstance(min_sup, (bool, np.bool_)):
        raise TypeError(f"min_sup must be a number, got bool {min_sup!r}")
    if isinstance(min_sup, (int, np.integer)):
        if min_sup < 1:
            raise ValueError(f"integer min_sup is an absolute count and must "
                             f"be >= 1, got {int(min_sup)}")
        return int(min_sup)
    f = float(min_sup)
    if 0.0 < f <= 1.0:
        return max(1, int(math.ceil(f * n_txn)))
    if f > 1.0:
        if not f.is_integer():
            raise ValueError(
                f"float min_sup > 1 is an absolute count and must be "
                f"integral (truncating {min_sup!r} would lower the "
                f"threshold); pass an int or a fraction in (0, 1]")
        return int(f)
    raise ValueError(f"min_sup must be a fraction in (0, 1] or an absolute "
                     f"count >= 1, got {min_sup!r}")


@dataclasses.dataclass
class EclatConfig:
    min_sup: float                      # float in (0,1] = fraction; int >= 1 = count
    variant: str = "v4"
    p: int = 10                         # partitions for v4/v5/v6 (paper: p=10)
    tri_matrix: Optional[bool] = None   # None = auto (paper's triMatrixMode)
    tri_matrix_max_items: int = 4096    # auto threshold (paper: item-id range)
    use_diffsets: bool = False          # v6 only (dEclat); other variants reject it
    backend: str = "pallas"             # jnp | pallas | sharded | tidsharded | grid (named, never measured: DESIGN.md §6)
    shard: str = "pairs"                # mesh split: "pairs" (frontier replicated) | "words" (tid axis, DESIGN.md §7) | "grid" (pairs x words 2D mesh, DESIGN.md §8)
    mode: str = "all"                   # workload: all | closed | maximal (lineage post-filter, DESIGN.md §9)
    max_k: Optional[int] = None         # deepest itemset length to mine (>= 1); None = unbounded
    bucket_min: int = 128               # pair-buffer bucket-ladder floor (half-pow2 rungs; low floor = low padding waste)
    checkpoint_dir: Optional[str] = None
    checkpoint_every_level: bool = False

    def resolve_min_sup(self, n_txn: int) -> int:
        return resolve_min_sup(self.min_sup, n_txn)


@dataclasses.dataclass
class EclatResult:
    store: ItemsetStore
    db: Optional[VerticalDB]            # None when resumed from a checkpoint
    stats: dict
    mode: str = "all"                   # the workload mode this run mined for

    @property
    def counts(self) -> List[int]:
        return self.store.counts

    @property
    def total(self) -> int:
        return self.store.total

    def itemsets(self):
        return self.store.itemsets()

    def support_map(self):
        """The full frequent map (every mode mines the whole lattice —
        closed/maximal are post-filters over it, see :meth:`workload_map`).
        Each call adds its time to ``stats["phase_s"]["support_map"]``."""
        with _span("support_map", self.stats["phase_s"]):
            return self.store.support_map()

    def workload_map(self):
        """The mode-filtered map this run was configured for: the full
        frequent map for ``mode="all"``, its closed or maximal subset
        otherwise (DESIGN.md §9)."""
        from .postfilter import filter_mode
        return filter_mode(self.store.support_map(), self.mode)


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def run_bottom_up(
    execu: eng.Engine,
    store: ItemsetStore,
    lvl_bitmaps: jax.Array,
    class_id: np.ndarray,
    item_rank: np.ndarray,
    partition: np.ndarray,
    support: np.ndarray,
    *,
    abs_min_sup: int,
    mode: int,
    max_k: int,
    part_to_dev: np.ndarray,
    on_level=None,
) -> None:
    """Levels >= 3: per-class level-wise expansion (the paper's Phase-4).

    One shared loop drives both the batch miner and the streaming miner —
    the streaming/batch bit-exactness contract (DESIGN.md §5) depends on
    the survivor bookkeeping below staying identical, so it exists once.
    Starts from a level-2 frontier (``class_id``/``item_rank``/``partition``/
    ``support`` row-aligned with ``lvl_bitmaps``) and appends one
    ``LevelRecord`` per surviving level; ``on_level`` (checkpointing) sees
    every new frontier.
    """
    k = 2
    while support.shape[0] and k < max_k:
        starts, sizes = class_segments(class_id)
        left, right = segment_pairs(starts, sizes)
        if left.size == 0:
            break
        k += 1
        with span("level", prefix=execu.trace_prefix, k=k,
                  pairs=int(left.size)):
            res = execu.expand(
                lvl_bitmaps, left.astype(np.int32), right.astype(np.int32),
                support[left].astype(np.int32),
                mode=mode, min_sup=abs_min_sup,
                device_of_pair=part_to_dev[partition[left]],
            )
            if not res.mask.any():
                break
            sel = np.nonzero(res.mask)[0]
            parent = left[sel]
            item_rank = item_rank[right[sel]]
            class_id = left[sel]
            partition = partition[left[sel]]
            support = res.supports
            store.add_level(LevelRecord(k=k, parent=parent,
                                        item_rank=item_rank,
                                        support=support, partition=partition))
            lvl_bitmaps = res.bitmaps
            if on_level is not None:
                on_level(k, class_id, item_rank, partition, support,
                         lvl_bitmaps)


def _build_db(transactions, n_items, abs_min_sup, spec, mesh, execu=None,
              phase_s=None) -> Tuple[VerticalDB, dict]:
    """The variant's vertical build: v2+ filter transactions before the
    pack, v3+ merge the per-shard partials by ``psum`` when given a mesh.
    Under a word-sharded engine every variant builds per shard on the
    device (:func:`build_vertical_sharded`, the ``shard_build`` span), so
    the frontier is born placed.  ``filter_reduction`` is the share of
    transactions the filter removed (paper §5.2.1 reports e.g.
    3.2%..25.8% for T40I10D100K)."""
    word_axis = getattr(execu, "data_axis", None)
    if word_axis is not None:
        with _span("shard_build", phase_s):
            db = build_vertical_sharded(
                transactions, n_items, abs_min_sup, order="support_asc",
                filter_txns=spec["filter_txns"], mesh=execu.mesh,
                axis=word_axis)
    else:
        db = build_vertical(transactions, n_items, abs_min_sup,
                            order="support_asc",
                            filter_txns=spec["filter_txns"],
                            mesh=mesh if spec["accumulator"] else None)
    info: dict = {}
    if spec["filter_txns"]:
        n_txn = bm.n_transactions(transactions)
        info["filter_reduction"] = 1.0 - db.n_txn / n_txn if n_txn else 0.0
    return db, info


def _finish(store: ItemsetStore, db: VerticalDB, stats: dict,
            config: EclatConfig) -> EclatResult:
    """Common tail of every ``mine()`` return path: record the workload
    mode (and, for closed/maximal, the post-filtered count — the filter
    itself is lazy via :meth:`EclatResult.workload_map`).  It runs inside
    the ``mine`` span, so ``total_s`` covers the mode bookkeeping too."""
    stats["mode"] = config.mode
    res = EclatResult(store=store, db=db, stats=stats, mode=config.mode)
    if config.mode != "all":
        stats["mode_itemsets"] = len(res.workload_map())
    return res


def mine(
    transactions: Union[Sequence[Sequence[int]], bm.CSR],
    n_items: int,
    config: EclatConfig,
    mesh: Optional[jax.sharding.Mesh] = None,
) -> EclatResult:
    """Mine all frequent itemsets.  ``transactions`` are item-id lists or a
    :class:`~repro.core.bitmap.CSR` pair ``(offsets, items)``, with the
    same answer.  ``mesh`` enables the mesh-mapped backends
    (``config.shard`` picks pair-, word-, or 2D grid-sharding).

    ``stats["phase_s"]`` times the phases, each a ``mine.<phase>`` span of
    the profiler trace inside the call's ``mine`` span, whose length is
    ``stats["total_s"]``: ``vertical`` (the vertical build), ``plan``
    (store, partitions, frontier upload), ``tri_matrix`` (level 2,
    holding ``cooc``, the co-occurrence pass), ``bottom_up`` (levels >= 3,
    one ``mine.level`` span each) and ``expand_wait`` (the host blocked on
    the engine's reads, inside the last two).  Under a word-sharded engine
    ``vertical`` holds ``shard_build`` (the per-shard device build, from
    the CSR upload to the placed frontier) and the co-occurrence pass is
    ``shard_cooc`` (shard-local counts, one psum, one read).
    ``stats["counts"]`` holds ``incidences`` (distinct (transaction, item)
    bits the vertical build scattered), ``host_reads`` (blocking
    device->host reads), ``psum_calls`` (the collectives the mine issued)
    and ``host_bitmap_bytes`` (bitmap bytes that crossed the host)."""
    wall: dict = {}
    with span("mine", wall, prefix="", n_txn=bm.n_transactions(transactions),
              n_items=int(n_items)):
        res = _mine(transactions, n_items, config, mesh)
    res.stats["total_s"] = wall["mine"]
    return res


def _mine(transactions, n_items, config, mesh) -> EclatResult:
    spec = VARIANTS[config.variant]
    if config.use_diffsets and config.variant != "v6":
        # every variant but v6 mines tidsets; silently dropping the flag
        # would hand back correct-looking results from a different algorithm
        raise ValueError(
            f"use_diffsets is only supported by variant 'v6' (dEclat); "
            f"variant {config.variant!r} would silently ignore it")
    if config.max_k is not None and config.max_k < 1:
        raise ValueError(f"max_k must be >= 1 (or None for unbounded), "
                         f"got {config.max_k}")
    from .postfilter import WORKLOAD_MODES
    if config.mode not in WORKLOAD_MODES:
        raise ValueError(f"unknown workload mode {config.mode!r}; "
                         f"expected one of {WORKLOAD_MODES}")
    stats: dict = {"variant": config.variant, "phase_s": {}, "counts": {}}
    phase_s = stats["phase_s"]

    n_txn = bm.n_transactions(transactions)
    abs_min_sup = config.resolve_min_sup(n_txn)
    stats["abs_min_sup"] = abs_min_sup
    execu = eng.resolve_engine(config.backend, mesh,
                               bucket_min=config.bucket_min,
                               shard=config.shard)
    word_axis = getattr(execu, "data_axis", None)

    # ---- Phase 1 (+2 filtering / +3 accumulator): vertical DB -------------
    with _span("vertical", phase_s):
        db, info = _build_db(transactions, n_items, abs_min_sup, spec, mesh,
                             execu, phase_s)
    stats.update(info)
    stats["counts"]["incidences"] = db.n_incidences
    stats["counts"]["host_bitmap_bytes"] = db.host_bitmap_bytes
    stats["counts"]["psum_calls"] = db.psum_calls
    n1, w = db.n_items, db.n_words
    stats["n_freq_items"] = n1
    stats["n_words"] = w

    with _span("plan", phase_s):
        store = ItemsetStore(db.items)
        # partition table over 1-length-prefix classes (class rank r, r < n1-1)
        n_classes = max(n1 - 1, 0)
        sizes1 = (n1 - 1 - np.arange(n_classes)).clip(min=0)
        est = pair_work(sizes1 + 1, w)  # +1: member count of class r is n1-1-r
        eff_p = config.p if spec["partitioner"] in ("hash", "reverse_hash", "greedy") else max(n_classes, 1)
        table = assign_partitions(n_classes, spec["partitioner"], eff_p, work=est)
        stats["backend"] = execu.name
        stats["backend_requested"] = config.backend
        # partition -> device round robin (mesh-mapped backends' pair axis)
        part_to_dev = np.arange(eff_p, dtype=np.int64) % max(execu.n_devices, 1)

        # balance of the *estimated* class work that drove partitioning (the
        # pair_work model the partitioners optimized), not a uniform per-pair
        # weight — so the reported efficiency reflects the actual assignment.
        # Recorded up front so every return path (max_k=1, single frequent
        # item, full run) carries the same stats shape.
        if n_classes > 0:
            pstats = partition_stats(table, est, eff_p)
            stats["partition_balance"] = {
                **{k_: v for k_, v in pstats.items() if k_ != "loads"},
                "estimated_loads": pstats["loads"].tolist(),
            }

        lvl1_partition = np.concatenate([table, [table[-1] if n_classes else 0]])[:n1] if n1 else np.zeros(0, np.int64)
        store.add_level(
            LevelRecord(
                k=1,
                parent=np.full(n1, -1, np.int64),
                item_rank=np.arange(n1, dtype=np.int64),
                support=db.supports.astype(np.int64),
                partition=lvl1_partition,
            )
        )
        # max_k bounds every level, including 2: with max_k=1 the frequent
        # items are the whole answer (the regression was recording level 2
        # regardless)
        max_k = n1 if config.max_k is None else config.max_k
        deep = n1 >= 2 and max_k >= 2
        if deep:
            # place the level-1 frontier the way the backend carries it,
            # once — level 2 may expand it in several kernel-sized calls, and
            # per-call placement (a word-axis reshard for tidsharded) would
            # repeat for each; a per-shard build is already placed
            bitmaps = execu.prepare_frontier(
                db.bitmaps if isinstance(db.bitmaps, jax.Array)
                else jax.device_put(db.bitmaps))
    if not deep:
        merge_stats(stats, execu.stats())
        return _finish(store, db, stats, config)
    diffsets = config.use_diffsets

    # ---- Phase 2: triangular matrix (2-itemset counts) --------------------
    with _span("tri_matrix", phase_s):
        tri = config.tri_matrix
        if tri is None:
            tri = n1 <= config.tri_matrix_max_items  # paper's BMS1/BMS2 opt-out
        stats["tri_matrix"] = bool(tri)

        sup1 = db.supports.astype(np.int32)
        mode2 = eng.MODE_TID_TO_DIFF if diffsets else eng.MODE_TIDSET
        if tri:
            if word_axis is None:
                with _span("cooc", phase_s):
                    counts2 = cooccurrence_counts(bitmaps)
            else:
                with _span("shard_cooc", phase_s):
                    counts2 = cooccurrence_counts(bitmaps, mesh=execu.mesh,
                                                  axis=word_axis)
                stats["counts"]["psum_calls"] += 1
            stats["counts"]["host_reads"] = cooc_blocks(n1)
            iu, ju, _ = frequent_pairs(counts2, abs_min_sup)
        else:
            # all pairs (the paper's no-tri-matrix path for BMS datasets); the
            # engine splits them into kernel-sized calls
            iu, ju = np.triu_indices(n1, k=1)
        res = execu.expand(
            bitmaps, iu.astype(np.int32), ju.astype(np.int32), sup1[iu],
            mode=mode2, min_sup=abs_min_sup,
            device_of_pair=part_to_dev[table[iu]] if iu.size else None,
        )
        # with the tri-matrix every pre-filtered pair must pass the engine's
        # threshold again: the level-2 record aligns iu/ju (all pre-filtered
        # pairs) with res.supports (survivors only), and a corrupt count matrix
        # would misalign every deeper level silently.  Same contract as the
        # streaming miner's cached-count check — a real exception, not an
        # ``assert``, so it fires under ``python -O``.
        if tri and iu.size and not res.mask.all():
            bad = np.nonzero(~res.mask)[0]
            raise RuntimeError(
                f"triangular-matrix co-occurrence counts disagree with the "
                f"engine on {bad.size}/{res.mask.size} level-2 pair(s) "
                f"(first: item ranks {int(iu[bad[0]])},{int(ju[bad[0]])}) — "
                f"the tri-matrix pass is corrupt")
        iu = iu[res.mask].astype(np.int64)
        ju = ju[res.mask].astype(np.int64)
        sup2 = res.supports.astype(np.int32)
        lvl_bitmaps = res.bitmaps

    parent = iu.copy()
    item_rank = ju.copy()
    class_id = iu.copy()
    partition = table[iu] if iu.size else np.zeros(0, np.int64)
    support = sup2.astype(np.int64)
    store.add_level(LevelRecord(k=2, parent=parent, item_rank=item_rank,
                                support=support, partition=partition))

    # ---- Phase 3/4: level-wise Bottom-Up -----------------------------------
    with _span("bottom_up", phase_s):
        mode_k = eng.MODE_DIFFSET if diffsets else eng.MODE_TIDSET
        on_level = None
        if config.checkpoint_dir and config.checkpoint_every_level:
            # resume metadata: everything resume_mine needs that is not
            # derivable from the frontier arrays themselves (DESIGN.md §10)
            on_level = _level_checkpointer(
                config.checkpoint_dir, store,
                {"abs_min_sup": int(abs_min_sup), "engine_mode": int(mode_k),
                 "max_k": int(max_k), "eff_p": int(eff_p),
                 "use_diffsets": bool(diffsets)})
        run_bottom_up(execu, store, lvl_bitmaps, class_id, item_rank,
                      partition, support, abs_min_sup=abs_min_sup,
                      mode=mode_k, max_k=max_k, part_to_dev=part_to_dev,
                      on_level=on_level)

    merge_stats(stats, execu.stats())
    return _finish(store, db, stats, config)


def _level_checkpointer(ckpt_dir: str, store: ItemsetStore, meta: dict):
    """``run_bottom_up``'s ``on_level``: a resumable checkpoint per level."""
    from .lineage import save_mining_checkpoint

    def on_level(k, class_id, item_rank, partition, support, lvl_bitmaps):
        # slice the rung padding off on device before the host transfer
        save_mining_checkpoint(ckpt_dir, store, k, class_id, item_rank,
                               partition, support,
                               jax.device_get(lvl_bitmaps[: support.shape[0]]),
                               meta=meta)
    return on_level


def resume_mine(
    config: EclatConfig,
    mesh: Optional[jax.sharding.Mesh] = None,
) -> EclatResult:
    """Continue a batch mine from its deepest per-level checkpoint.

    Reads the newest ``mining_ckpt_k*.npz`` under ``config.checkpoint_dir``
    (written by ``mine()`` with ``checkpoint_every_level=True``), rebuilds
    the store and frontier, and resumes ``run_bottom_up`` from the
    checkpointed level.  The engine is resolved fresh from *this* process's
    ``config.backend`` / ``config.shard`` / ``mesh`` — restore onto fewer
    devices, a different grid factorization, or a single device, and the
    frontier is re-placed by ``prepare_frontier`` under the new specs
    (DESIGN.md §10): the remaining levels come out bit-exact because every
    backend is bit-exact on the same frontier.  The original transactions
    are not needed; ``EclatResult.db`` is ``None`` on a resumed run.
    """
    wall: dict = {}
    with span("mine", wall, prefix="", resumed=1):
        res = _resume_mine(config, mesh)
    res.stats["total_s"] = wall["mine"]
    return res


def _resume_mine(config: EclatConfig, mesh) -> EclatResult:
    from .lineage import latest_mining_checkpoint, load_mining_checkpoint

    if not config.checkpoint_dir:
        raise ValueError("resume_mine needs config.checkpoint_dir")
    path = latest_mining_checkpoint(config.checkpoint_dir)
    store, fr = load_mining_checkpoint(path)
    meta = fr.get("meta") or {}
    if "abs_min_sup" not in meta:
        raise ValueError(
            f"{path} predates resume metadata — re-run the original mine "
            f"with this version to write a resumable checkpoint")
    abs_min_sup = int(meta["abs_min_sup"])
    mode_k = int(meta["engine_mode"])
    max_k = int(meta["max_k"])
    eff_p = int(meta["eff_p"])
    stats: dict = {"variant": config.variant, "phase_s": {},
                   "abs_min_sup": abs_min_sup,
                   "resumed_from": path, "resume_k": int(fr["k"])}

    execu = eng.resolve_engine(config.backend, mesh,
                               bucket_min=config.bucket_min,
                               shard=config.shard)
    stats["backend"] = execu.name
    stats["backend_requested"] = config.backend
    part_to_dev = np.arange(eff_p, dtype=np.int64) % max(execu.n_devices, 1)
    lvl_bitmaps = execu.prepare_frontier(jax.device_put(fr["bitmaps"]))

    on_level = (_level_checkpointer(config.checkpoint_dir, store, meta)
                if config.checkpoint_every_level else None)

    with _span("bottom_up", stats["phase_s"]):
        run_bottom_up(execu, store, lvl_bitmaps,
                      class_id=np.asarray(fr["class_id"]),
                      item_rank=np.asarray(fr["item_rank"]),
                      partition=np.asarray(fr["partition"]),
                      support=np.asarray(fr["support"]).astype(np.int64),
                      abs_min_sup=abs_min_sup, mode=mode_k, max_k=max_k,
                      part_to_dev=part_to_dev, on_level=on_level)
    merge_stats(stats, execu.stats())
    return _finish(store, None, stats, config)
