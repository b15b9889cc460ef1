"""The device-resident mining engine: pluggable executors for the Eclat hot loop.

``core.eclat.mine`` is pure driver logic (class segmentation, partition
tables, store bookkeeping); every device-side intersection goes through the
backend interface defined here.  A backend turns one level-expansion request

    (frontier bitmaps, pair lists, parent supports, mode, min_sup)

into a :class:`LevelResult`: the survivor mask and supports for the driver
plus the survivor bitmaps, compacted *on device* — the padded ``(Q, W)``
intersection never crosses the host boundary.

Backends (``register_backend`` registry, selected by ``EclatConfig.backend``):

  jnp      reference executor — ``jnp.take`` gather + AND + popcount, the
           semantics every other backend must match bit-exactly.
  pallas   fused executor — one ``pallas_call`` (kernels.fused_intersect)
           gathers rows by scalar-prefetch index maps, intersects, popcounts
           and applies the min-support threshold in one executable on TPU;
           off-TPU it dispatches to the identically-fused jnp path
           (``stats()["kernel_path"]`` says which ran).  Default.
  sharded  shard_map-over-either: pairs are grouped by the device their
           equivalence class was partitioned to, padded per device to a
           common bucket, and executed under ``shard_map`` — the paper's
           executor-task mapping.  Constructed automatically when ``mine``
           receives a mesh.
  tidsharded  word-sharded (tid-axis) execution: the frontier bitmap is
           carried as ``P(None, "data")`` — every device holds all rows but
           only a word slice — each shard intersects and popcounts its
           slice, supports are recovered with one psum, and survivor
           compaction stays shard-local.  Per-device frontier memory is
           total/n_shards, so windows larger than one device's memory stay
           minable (DESIGN.md §7).  Selected by ``shard="words"``.
  grid     grid-sharded execution on a 2D ``("class", "data")`` mesh:
           candidate pairs split over the class axis (as in ``sharded``)
           AND the frontier's word axis split over the data axis (as in
           ``tidsharded``), so per-device pair work drops ~1/n_class and
           per-device frontier memory ~1/n_data at the same time — the
           first backend that composes both shard_map axes (DESIGN.md §8).
           Selected by ``shard="grid"``.

Axis ownership (who interprets what): ``device_of_pair`` always routes over
the backend's *pair* axis (``n_devices`` wide — the class axis for
``sharded``/``grid``, trivial for the rest); ``prepare_frontier``/``_take``
own the *word* axis placement (``P(None, data)`` for ``tidsharded``/
``grid``, identity otherwise); ``_compact`` is axis-agnostic and delegates
the row gather to ``_take``.  The shared helpers ``group_pairs_by_device``
and ``_WordShardedFrontierMixin`` implement one axis each, so a backend
composes them instead of copy-pasting an engine.

Bucket ladder: pair batches are padded up to a half-power-of-two ladder
(``bucket_min`` x {1, 1.5, 2, 3, 4, 6, 8, ...}), so every XLA/Mosaic
executable is compiled once per rung and reused across levels while
worst-case padding stays under ~33% (vs ~50% on the pure pow2 ladder); the
padded host-side index buffers themselves are persistent per rung (no
per-call allocation or ``argsort`` churn for the single-device backends).
The default floor is 128 — the ladder is discrete, so a low floor costs at
most a handful of extra one-time compiles, while a high one (the old 1024)
pads every small level up to 1024 pairs.
No rung exceeds ``MAX_PAIRS_PER_CALL`` (the kernel's SMEM bound), nor the
pairs whose ``(Q, W)`` block fits a device's share of its memory
(:func:`block_budget`): ``Engine.expand`` runs a larger level as several
capped calls.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, List, Optional, Tuple, Type

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..dist.compat import shard_map, shard_map_unchecked
from ..dist.sharding import (grid_block_spec, grid_pair_spec, mesh_descriptor,
                             shard_words, word_shard_spec)
from ..kernels.fused_intersect import (MAX_PAIRS_PER_CALL, MODE_DIFFSET,
                                       MODE_TID_TO_DIFF, MODE_TIDSET,
                                       compact_epilogue, fused_intersect,
                                       fused_intersect_compact,
                                       fused_intersect_compact_ref,
                                       fused_intersect_partial,
                                       fused_intersect_partial_ref,
                                       fused_intersect_ref, kernel_path,
                                       round_up_lanes)
from ..spans import span

__all__ = [
    "MODE_TIDSET", "MODE_TID_TO_DIFF", "MODE_DIFFSET",
    "LevelResult", "Engine", "EngineState", "JnpEngine", "PallasEngine",
    "ShardedEngine", "TidShardedEngine", "GridShardedEngine",
    "group_pairs_by_device", "register_backend", "available_backends",
    "block_budget", "call_cap",
    "make_engine", "engine_from_state", "resolve_engine", "merge_stats",
    "MAX_PAIRS_PER_CALL",
]


def _dput(x, sharding=None) -> jax.Array:
    """Explicit host->device upload.  The expand hot loops never rely on
    implicit ``jnp.asarray`` conversion of host state (staticcheck RS005),
    so steady-state mining runs clean under ``jax.transfer_guard``.  Mesh
    backends pass the placement their executor declares (replicated or
    pair-split) — without it the upload lands on one device and dispatch
    would re-shard implicitly, which the guard also forbids."""
    return jax.device_put(x, sharding)


def _dput_i32(v, sharding=None) -> jax.Array:
    """Explicit scalar upload as a strong-typed int32 (see :func:`_dput`)."""
    return jax.device_put(np.int32(v), sharding)


# ---------------------------------------------------------------------------
# result type + bucket-ladder pair buffers
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class LevelResult:
    """One level expansion, already min-support filtered.

    mask:     (Q,) bool — which input pairs survived, in input pair order.
    supports: (S,) int64 — supports of the survivors (S = mask.sum()).
    bitmaps:  (Sb, W) uint32 device array — survivor tidsets/diffsets,
              compacted on device into a power-of-two row rung Sb >= S.
              Rows [:S] are the survivors in mask order; rows [S:] are
              padding (duplicates of row 0) and must not be read.  Padding
              the compaction keeps device shapes on the same bucket ladder
              as the pair batches, so steady-state mining (and every window
              slide of the streaming miner) reuses compiled executables
              instead of recompiling per survivor count.
    """

    mask: np.ndarray
    supports: np.ndarray
    bitmaps: jax.Array


@dataclasses.dataclass
class EngineState:
    """Serializable engine state (DESIGN.md §10): config + accounting as
    *data*, never Python object innards.

    What is data: the knobs a rebuild needs (backend / inner executor /
    ladder floors) and the accounting ledgers that must survive a crash so
    per-slide ``stats(since=...)`` deltas stay truthful after recovery.
    What is derived (and therefore absent): pair buffers, compiled
    shard_map executors, shardings, tile widths — all reconstructed by
    :func:`engine_from_state` under whatever mesh the restoring process
    brings.  ``mesh`` is the provenance descriptor of the mesh the snapshot
    ran on; it is reported, never restored from.  A tree written before the
    ``block_w`` / ``compact`` / ``autotune`` knobs were retired still
    restores: :meth:`from_tree` ignores those keys.
    """
    backend: str
    inner: str
    bucket_min: int
    compact_min: int
    interpret: Optional[bool]
    mesh: Optional[dict]                      # mesh_descriptor provenance
    n_intersections: int
    n_padded: int
    level_padding: List[Tuple[int, int]]
    device_pair_counts: List[np.ndarray]

    def to_tree(self) -> Tuple[Dict[str, np.ndarray], dict]:
        """(array tree, JSON-able extra) for ``training.checkpoint``."""
        tree: Dict[str, np.ndarray] = {
            "level_padding": np.asarray(self.level_padding,
                                        np.int64).reshape(-1, 2),
        }
        if self.device_pair_counts:
            tree["device_pair_counts"] = np.stack(
                [np.asarray(c, np.int64) for c in self.device_pair_counts])
        extra = {"backend": self.backend, "inner": self.inner,
                 "bucket_min": int(self.bucket_min),
                 "compact_min": int(self.compact_min),
                 "interpret": self.interpret, "mesh": self.mesh,
                 "n_intersections": int(self.n_intersections),
                 "n_padded": int(self.n_padded)}
        return tree, extra

    @classmethod
    def from_tree(cls, tree: Dict[str, np.ndarray], extra: dict) -> "EngineState":
        lp = np.asarray(tree["level_padding"], np.int64).reshape(-1, 2)
        dpc = tree.get("device_pair_counts")
        return cls(
            backend=str(extra["backend"]), inner=str(extra["inner"]),
            bucket_min=int(extra["bucket_min"]),
            compact_min=int(extra["compact_min"]),
            interpret=extra["interpret"], mesh=extra["mesh"],
            n_intersections=int(extra["n_intersections"]),
            n_padded=int(extra["n_padded"]),
            level_padding=[(int(a), int(b)) for a, b in lp],
            device_pair_counts=([np.asarray(c, np.int64) for c in dpc]
                                if dpc is not None else []))


def bucket_size(n: int, floor: int) -> int:
    """Smallest ladder rung >= n (>= floor).

    The ladder is half-power-of-two: ``floor * {1, 1.5, 2, 3, 4, 6, 8, ...}``
    rather than pure doubling.  Pure powers of two waste up to ~50% of every
    padded batch in the worst case (n just past a rung); the 1.5x
    intermediate rungs cap that at ~33% for ~2x the executable count;
    level-1/2 frontier counts routinely land just past a power of two."""
    b = max(int(floor), 1)
    while b < n:
        h = b + (b >> 1)
        if n <= h:
            return h
        b <<= 1
    return b


def rung_at_most(n: int, floor: int) -> int:
    """Largest ladder rung <= ``n`` (the floor where ``n`` is below it)."""
    best = b = max(int(floor), 1)
    while b <= n:
        best = b
        h = b + (b >> 1)
        if h > b and h <= n:
            best = h
        b <<= 1
    return best


# the devices' memory when a device reports none (the CPU): a TPU v5e's
DEFAULT_DEVICE_BYTES = 16 * 10**9


def block_budget(device) -> int:
    """Bytes of one call's padded ``(Q, W)`` intersection block a device may
    hold: a quarter of its memory.  The kernel writes the block and the
    compaction epilogue a copy of it, so the call takes at most half, and
    the frontier it reads and the survivors it leaves keep the rest."""
    stats = device.memory_stats() or {}
    return int(stats.get("bytes_limit", DEFAULT_DEVICE_BYTES)) // 4


def call_cap(row_bytes: int, budget: int, floor: int) -> int:
    """Pairs one call may carry: a ladder rung, at most
    ``MAX_PAIRS_PER_CALL`` (SMEM) and at most the rows of ``row_bytes``
    that ``budget`` holds (HBM)."""
    fit = max(int(budget) // max(int(row_bytes), 1), 1)
    return min(rung_at_most(fit, floor), MAX_PAIRS_PER_CALL)


def pair_bucket(n: int, floor: int) -> int:
    """Ladder rung for a pair batch of ``n <= MAX_PAIRS_PER_CALL`` pairs,
    clipped to that cap: the kernel's scalar-prefetched indices must fit
    its SMEM (``kernels.fused_intersect.MAX_PAIRS_PER_CALL``), so no rung
    above it is ever issued.  :meth:`Engine.expand` splits larger levels."""
    return min(bucket_size(n, floor), MAX_PAIRS_PER_CALL)


class PairBuffers:
    """Persistent bucket-ladder host buffers for padded pair batches.

    One (left, right, sup_left) int32 triple per rung, reused across levels:
    refilling in place avoids the per-call allocation the old executor paid,
    and the power-of-two rungs keep the jit cache to O(log Q) entries.
    """

    def __init__(self, floor: int):
        self.floor = max(int(floor), 1)
        self._rungs: Dict[int, Tuple[np.ndarray, np.ndarray, np.ndarray]] = {}

    def fill(self, left: np.ndarray, right: np.ndarray, sup_left: np.ndarray):
        q = int(left.shape[0])
        qb = pair_bucket(q, self.floor)
        rung = self._rungs.get(qb)
        if rung is None:
            rung = tuple(np.zeros(qb, np.int32) for _ in range(3))
            self._rungs[qb] = rung
        l, r, s = rung
        l[:q], r[:q], s[:q] = left, right, sup_left
        l[q:] = 0
        r[q:] = 0
        s[q:] = 0
        return qb, l, r, s


def group_pairs_by_device(
    left: np.ndarray,
    right: np.ndarray,
    sup_left: np.ndarray,
    device_of_pair: Optional[np.ndarray],
    n_devices: int,
    floor: int,
) -> Tuple[int, np.ndarray, np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Group candidate pairs by their assigned pair-axis slot and pad every
    slot's block to a shared ladder rung.

    The pair-axis half of the mesh-mapped backends (``sharded`` distributes
    over its one axis, ``grid`` over its class axis): returns ``(qmax, lpad,
    rpad, spad, slot_of_pair, counts)`` where the ``(n_devices, qmax)`` pad
    blocks hold each device's pairs, ``slot_of_pair[q] = dev * qmax + slot``
    maps input pair order to padded-block position, and ``counts`` is the
    per-device pair load (the balance stats input).  Out-of-range device ids
    are refused up front: one would fall outside the grouping loop and leave
    its ``slot_of_pair`` entry uninitialized — garbage slots, silently wrong
    supports.
    """
    q = int(left.shape[0])
    d = int(n_devices)
    if device_of_pair is None:
        device_of_pair = np.zeros(q, np.int64)
    device_of_pair = np.asarray(device_of_pair, np.int64)
    if device_of_pair.shape != (q,):
        raise ValueError(f"device_of_pair must be shape ({q},), got "
                         f"{device_of_pair.shape}")
    if (device_of_pair < 0).any() or (device_of_pair >= d).any():
        bad = device_of_pair[(device_of_pair < 0) | (device_of_pair >= d)]
        raise ValueError(
            f"device_of_pair contains ids outside [0, {d}) for this "
            f"{d}-device pair axis: {np.unique(bad).tolist()[:8]}")
    order = np.argsort(device_of_pair, kind="stable")
    counts = np.bincount(device_of_pair, minlength=d)
    qmax = pair_bucket(int(counts.max()), floor)
    lpad = np.zeros((d, qmax), np.int32)
    rpad = np.zeros((d, qmax), np.int32)
    spad = np.zeros((d, qmax), np.int32)
    # every slot is written by the grouping loop below — the range check
    # above refuses the one id class that could leave a hole
    slot_of_pair = np.empty(q, np.int64)  # staticcheck: disable=RS002
    off = 0
    for dev in range(d):
        c = int(counts[dev])
        idx = order[off: off + c]
        lpad[dev, :c] = left[idx]
        rpad[dev, :c] = right[idx]
        spad[dev, :c] = sup_left[idx]
        slot_of_pair[idx] = dev * qmax + np.arange(c)
        off += c
    return qmax, lpad, rpad, spad, slot_of_pair, counts


# ---------------------------------------------------------------------------
# backend registry
# ---------------------------------------------------------------------------

BACKENDS: Dict[str, Type["Engine"]] = {}


def register_backend(name: str):
    def deco(cls: Type["Engine"]) -> Type["Engine"]:
        BACKENDS[name] = cls
        cls.name = name
        return cls
    return deco


def available_backends() -> List[str]:
    return sorted(BACKENDS)


def make_engine(
    backend: str,
    *,
    mesh: Optional[jax.sharding.Mesh] = None,
    bucket_min: int = 128,
    interpret: Optional[bool] = None,
    inner: str = "pallas",
) -> "Engine":
    """Construct a backend by registry name.

    ``sharded`` / ``tidsharded`` / ``grid`` require a mesh (``grid`` a 2D
    one with ``("class", "data")`` axes); ``interpret`` forces the Pallas
    kernel's interpreter (tests) instead of the TPU/ref dispatch.
    """
    cls = BACKENDS.get(backend)
    if cls is None:
        raise ValueError(f"unknown engine backend {backend!r}; "
                         f"available: {available_backends()}")
    if backend in ("sharded", "tidsharded", "grid"):
        if mesh is None:
            raise ValueError(f"{backend} backend requires a mesh")
        return cls(mesh, bucket_min=bucket_min, inner=inner,
                   interpret=interpret)
    if backend == "pallas":
        return PallasEngine(bucket_min=bucket_min, interpret=interpret)
    return cls(bucket_min=bucket_min)


_UNSET = object()


def engine_from_state(
    state: EngineState,
    mesh: Optional[jax.sharding.Mesh] = None,
    *,
    backend: Optional[str] = None,
    interpret=_UNSET,
) -> "Engine":
    """Rebuild an engine from an :class:`EngineState`, possibly on a
    different mesh — the engine half of live re-meshing (DESIGN.md §10).

    The snapshot's mesh descriptor is provenance only: the rebuilt engine is
    constructed against ``mesh`` (whatever factorization the restoring
    process brings), so a ``tidsharded`` state taken on 4 devices restores
    onto a 2-device mesh, a ``grid`` state taken on 2x2 onto 4x1, and any
    mesh-mapped state onto a single device (``mesh=None`` falls back to the
    snapshot's inner executor).  ``backend`` overrides the target backend
    outright (cross-family re-meshing, e.g. ``sharded`` -> ``tidsharded``);
    ``interpret`` overrides the kernel-interpreter flag (tests).
    """
    target = state.backend if backend is None else backend
    mesh_backends = ("sharded", "tidsharded", "grid")
    if target in mesh_backends and mesh is None:
        target = state.inner if state.inner in ("jnp", "pallas") else "pallas"
    interp = state.interpret if interpret is _UNSET else interpret
    eng = make_engine(target,
                      mesh=mesh if target in mesh_backends else None,
                      bucket_min=state.bucket_min,
                      interpret=interp,
                      inner=state.inner)
    eng.compact_min = int(state.compact_min)
    return eng.restore_state(state)


def resolve_engine(
    backend: str,
    mesh: Optional[jax.sharding.Mesh] = None,
    *,
    bucket_min: int = 128,
    shard: str = "pairs",
) -> "Engine":
    """Map a (backend name, mesh, shard mode) request onto an engine.

    A mesh always means a mesh-mapped backend, with the named single-device
    backend as its inner executor; ``shard`` picks which axis (or axes) the
    mesh splits: ``"pairs"`` (ShardedEngine — candidate pairs distributed,
    the frontier replicated; the paper's executor mapping), ``"words"``
    (TidShardedEngine — the frontier's word axis distributed, pairs
    replicated; DESIGN.md §7), or ``"grid"`` (GridShardedEngine — pairs
    over a ``"class"`` axis AND words over a ``"data"`` axis of a 2D mesh;
    DESIGN.md §8).  ``"sharded"`` / ``"tidsharded"`` / ``"grid"`` without a
    mesh degrade gracefully to the single-device default (pallas).  Naming
    a mesh-mapped backend implies its shard mode (``sharded`` -> pairs,
    ``tidsharded`` -> words, ``grid`` -> grid); combining one with a
    *different* non-default ``shard`` is contradictory and rejected rather
    than silently resolved to either side.  Both the batch driver
    (``core.eclat.mine``) and the streaming miner (``repro.streaming``)
    resolve their executors here.  The backend is named, never measured
    (DESIGN.md §6): a name not in the registry raises.
    """
    shard_to_backend = {"pairs": "sharded", "words": "tidsharded",
                        "grid": "grid"}
    if shard not in shard_to_backend:
        raise ValueError(f"unknown shard mode {shard!r}; "
                         "expected 'pairs', 'words' or 'grid'")
    if backend not in BACKENDS:
        raise ValueError(f"unknown engine backend {backend!r}; "
                         f"available: {available_backends()}")
    implied = {"sharded": "pairs", "tidsharded": "words",
               "grid": "grid"}.get(backend)
    if implied is not None:
        # shard="pairs" is the config default, so only an explicit
        # disagreement is a conflict
        if shard not in ("pairs", implied):
            raise ValueError(
                f"backend {backend!r} implies shard={implied!r} but "
                f"shard={shard!r} was requested; drop one of the two")
        shard = implied
    if mesh is not None:
        inner = backend if backend in ("jnp", "pallas") else "pallas"
        return make_engine(shard_to_backend[shard], mesh=mesh,
                           bucket_min=bucket_min, inner=inner)
    if implied is not None:
        backend = "pallas"
    return make_engine(backend, bucket_min=bucket_min)


def merge_stats(stats: dict, engine_stats: dict) -> dict:
    """``stats.update(engine_stats)``, except that the engine's ``phase_s``
    and ``counts`` sub-dicts are added into the miner's own, key by key."""
    for key in ("phase_s", "counts"):
        for k, v in engine_stats.pop(key, {}).items():
            sub = stats.setdefault(key, {})
            sub[k] = sub.get(k, 0) + v
    stats.update(engine_stats)
    return stats


class Engine:
    """Backend interface + shared accounting.

    The fused kernel's word-tile width is derived per call shape from the
    cost model (``kernels.fused_intersect.ops.resolve_block_w``); the
    single-device and word-sharded backends compact survivors inside the
    fused executable (one dispatch, only survivors cross back).

    ``compact_min``  floor of the *survivor* bucket ladder — decoupled from
                 the pair-batch floor because survivor counts collapse fast
                 at deep levels; a 1024-row survivor rung for 12 survivors
                 would be almost all padding.
    """

    name = "abstract"

    def __init__(self, bucket_min: int = 128, *,
                 compact_min: Optional[int] = None):
        self.buffers = PairBuffers(bucket_min)
        self.compact_min = (min(self.buffers.floor, 128)
                            if compact_min is None else max(int(compact_min), 1))
        self.n_intersections = 0
        self.n_padded = 0
        self.device_pair_counts: List[np.ndarray] = []
        self.level_padding: List[Tuple[int, int]] = []
        self.call_shapes: set = set()
        self.n_devices = 1
        self.n_psums = 0
        # bytes of a call's block each device may hold (block_budget); the
        # mesh backends read their mesh's devices
        self.block_budget = block_budget(jax.devices()[0])
        # the row's words are split this many ways across devices
        self.word_split = 1
        # host time blocked on expansions (``expand_wait``) and the blocking
        # reads behind it: timings of this process, not engine state
        self.phase_s: Dict[str, float] = {}
        self.n_reads = 0
        # trace-name prefix of the miner that owns this engine
        self.trace_prefix = "mine."

    def _record_padding(self, q: int, padded: int) -> None:
        """Per-level pair-padding ledger behind ``stats()['pair_padding']``."""
        self.n_padded += padded - q
        self.level_padding.append((int(q), int(padded)))

    def _read(self, *arrays) -> tuple:
        """An expansion's one blocking device->host read, of all its
        ``arrays`` at once: the host waiting on the kernel, timed as
        ``expand_wait`` and counted in ``stats()["counts"]["host_reads"]``."""
        self.n_reads += 1
        with span("expand_wait", self.phase_s, prefix=self.trace_prefix):
            return jax.device_get(arrays)

    def expand(
        self,
        bitmaps: jax.Array,
        left: np.ndarray,
        right: np.ndarray,
        sup_left: np.ndarray,
        *,
        mode: int,
        min_sup: int,
        device_of_pair: Optional[np.ndarray] = None,
    ) -> LevelResult:
        """Intersect all (left[q], right[q]) frontier-row pairs, threshold at
        ``min_sup``, and return the device-compacted survivors.

        A batch above :meth:`call_cap` pairs is split into calls of at most
        that many; their survivors are concatenated in pair order, so the
        result is bit-exact with one unbounded call."""
        q = int(left.shape[0])
        if q == 0:
            return self._empty(bitmaps)
        cap = self.call_cap(bitmaps)
        parts = [
            self._expand_call(
                bitmaps, left[s:s + cap], right[s:s + cap],
                sup_left[s:s + cap], mode=mode, min_sup=min_sup,
                device_of_pair=(None if device_of_pair is None
                                else device_of_pair[s:s + cap]))
            for s in range(0, q, cap)]
        if len(parts) == 1:
            return parts[0]
        # each call's survivors lead its rung-padded block: gather them out
        # of the concatenated blocks into one survivor rung
        offsets = np.cumsum([0] + [p.bitmaps.shape[0] for p in parts[:-1]])
        sel = np.concatenate([o + np.arange(p.supports.shape[0])
                              for o, p in zip(offsets, parts)])
        block = jnp.concatenate([p.bitmaps for p in parts], axis=0)
        return LevelResult(
            mask=np.concatenate([p.mask for p in parts]),
            supports=np.concatenate([p.supports for p in parts]),
            bitmaps=self._compact(block, sel.astype(np.int32)))

    def call_cap(self, bitmaps) -> int:
        """Pairs per call over ``bitmaps``: capped by SMEM and by the bytes
        of the block on each device (its words split ``word_split`` ways,
        lane-padded), against ``block_budget``."""
        words = -(-int(bitmaps.shape[1]) // self.word_split)
        return call_cap(round_up_lanes(words) * 4, self.block_budget,
                        self.buffers.floor)

    def _expand_call(self, bitmaps, left, right, sup_left, *, mode, min_sup,
                     device_of_pair=None) -> LevelResult:
        """One kernel-sized batch (``0 < Q <= call_cap``)."""
        raise NotImplementedError

    def kernel_path(self) -> str:
        """Which executable this engine's expansions run (``mosaic``,
        ``interpret`` or ``xla-ref``; see ``kernels.fused_intersect.ops``)."""
        inner = getattr(self, "inner", self.name)
        if inner != "pallas":
            return "xla-ref"
        return kernel_path(getattr(self, "interpret", None))

    def _note_call(self, bitmaps: jax.Array, qb: int, mode: int) -> None:
        """Record a dispatched (rows, words, pairs, mode) kernel shape — one
        compiled executable each — for ``stats()["call_shapes"]``."""
        self.call_shapes.add((int(bitmaps.shape[0]), int(bitmaps.shape[1]),
                              int(qb), int(mode)))

    def _empty(self, bitmaps: jax.Array) -> LevelResult:
        w = bitmaps.shape[1]
        return LevelResult(mask=np.zeros(0, bool),
                           supports=np.zeros(0, np.int64),
                           bitmaps=jnp.zeros((0, w), jnp.uint32))

    def _take(self, block: jax.Array, idx: jax.Array) -> jax.Array:
        """Device row gather behind compaction; backends that must preserve
        a placement (tid-sharding) override only this."""
        return _take_rows(block, idx)

    def _compact(self, block: jax.Array, sel: np.ndarray) -> jax.Array:
        """Gather survivor rows ``sel`` out of ``block``, padded to a
        ladder rung (pad slots gather row 0) so the device gather and
        every downstream expansion see ladder shapes, not raw counts.
        Uses the survivor floor ``compact_min``, not the pair floor."""
        sb = bucket_size(max(int(sel.shape[0]), 1), self.compact_min)
        idx = np.zeros(sb, np.int32)
        idx[:sel.shape[0]] = sel
        return self._take(block, _dput(idx, getattr(self, "_rep_sharding",
                                                    None)))

    def _survivors(self, compact: jax.Array, mask_dev: jax.Array,
                   sup: jax.Array, q: int) -> LevelResult:
        """A fused-compaction call's result: one blocking read of its mask
        and supports, of which the first ``q`` are real pairs, and the
        survivors' rung-slice of ``compact``: rows ``[:S]`` are the
        survivors, the rung padding beyond them duplicates row 0 — the same
        convention :meth:`_compact` produces.  The rung is clipped to the
        block: a pair batch clipped to ``MAX_PAIRS_PER_CALL`` can be shorter
        than the survivor ladder's next rung."""
        mask_h, sup_h = self._read(mask_dev, sup)
        mask = mask_h[:q].astype(bool)
        sb = bucket_size(max(int(mask.sum()), 1), self.compact_min)
        return LevelResult(
            mask=mask, supports=sup_h[:q][mask].astype(np.int64),
            bitmaps=_prefix_rows(compact, min(sb, compact.shape[0])))

    def prepare_frontier(self, bitmaps: jax.Array) -> jax.Array:
        """Place a frontier the way this backend will carry it (identity for
        single-device backends).  Drivers that expand the same frontier many
        times (chunked level 2) call this once instead of paying per-call
        placement."""
        return bitmaps

    def snapshot_state(self) -> EngineState:
        """Serializable snapshot of config + accounting (DESIGN.md §10).
        Deep-copies the ledgers so the snapshot is stable while the engine
        keeps expanding."""
        return EngineState(
            backend=self.name,
            inner=getattr(self, "inner",
                          self.name if self.name in ("jnp", "pallas")
                          else "pallas"),
            bucket_min=self.buffers.floor,
            compact_min=self.compact_min,
            interpret=getattr(self, "interpret", None),
            mesh=mesh_descriptor(getattr(self, "mesh", None)),
            n_intersections=self.n_intersections,
            n_padded=self.n_padded,
            level_padding=[(int(a), int(b)) for a, b in self.level_padding],
            device_pair_counts=[np.asarray(c, np.int64).copy()
                                for c in self.device_pair_counts])

    def restore_state(self, state: EngineState) -> "Engine":
        """Adopt a snapshot's accounting.  Per-device pair counts are kept
        only when this engine's pair axis has the same width as the
        snapshot's — restoring onto a different mesh factorization makes the
        old per-device attribution meaningless, so it is dropped (derived
        accounting, not data; DESIGN.md §10)."""
        self.n_intersections = int(state.n_intersections)
        self.n_padded = int(state.n_padded)
        self.level_padding = [(int(a), int(b)) for a, b in state.level_padding]
        dpc = [np.asarray(c, np.int64).copy()
               for c in state.device_pair_counts]
        if any(c.shape[0] != self.n_devices for c in dpc):
            dpc = []
        self.device_pair_counts = dpc
        return self

    def snapshot(self) -> Tuple[int, int, int, int, int, float, int]:
        """Counter snapshot, for per-call deltas on a long-lived engine
        (``stats(since=snapshot)`` — the streaming miner reports per-slide
        work, not lifetime totals)."""
        return (self.n_intersections, self.n_padded,
                len(self.device_pair_counts), len(self.level_padding),
                self.n_reads, self.phase_s.get("expand_wait", 0.0),
                self.n_psums)

    def stats(self, since: Optional[Tuple] = None) -> dict:
        """Counters since ``since`` (or ever).  ``phase_s`` and ``counts``
        are sub-dicts a miner merges into its own (:func:`merge_stats`)."""
        i0, p0, d0, l0, r0, w0, s0 = ((tuple(since) + (0,) * 7)[:7]
                                      if since else (0,) * 7)
        out = {
            "backend": self.name,
            "kernel_path": self.kernel_path(),
            "n_intersections": self.n_intersections - i0,
            "n_padded": self.n_padded - p0,
        }
        if self.n_reads > r0:
            out["phase_s"] = {
                "expand_wait": self.phase_s.get("expand_wait", 0.0) - w0}
            out["counts"] = {"host_reads": self.n_reads - r0}
        if self.n_psums > s0:
            out.setdefault("counts", {})["psum_calls"] = self.n_psums - s0
        if self.call_shapes:
            out["call_shapes"] = sorted(list(c) for c in self.call_shapes)
        levels = self.level_padding[l0:]
        if levels:
            tot_q = sum(q for q, _ in levels)
            tot_p = sum(p for _, p in levels)
            out["pair_padding"] = {
                "per_level": [
                    {"pairs": q, "padded_to": p,
                     "efficiency": q / p if p else 1.0}
                    for q, p in levels
                ],
                "efficiency": tot_q / tot_p if tot_p else 1.0,
            }
        if self.device_pair_counts[d0:]:
            per_dev = np.sum(self.device_pair_counts[d0:], axis=0)
            out["device_balance"] = {
                "pairs_per_device": per_dev.tolist(),
                "padding_efficiency": float(
                    per_dev.sum() / (per_dev.max() * per_dev.shape[0]))
                if per_dev.max() > 0 else 1.0,
            }
        return out


# ---------------------------------------------------------------------------
# jnp reference backend
# ---------------------------------------------------------------------------

@jax.jit
def _take_rows(arr: jax.Array, idx: jax.Array) -> jax.Array:
    return jnp.take(arr, idx, axis=0)


@functools.partial(jax.jit, static_argnames=("n",))
def _prefix_rows(arr: jax.Array, n: int) -> jax.Array:
    # static-size prefix slice: an eager ``arr[:n]`` dispatches dynamic-slice
    # with host scalar starts — an implicit h2d the steady-state transfer
    # guard forbids (staticcheck SH002)
    return jax.lax.slice_in_dim(arr, 0, n, axis=0)


@register_backend("jnp")
class JnpEngine(Engine):
    """XLA reference executor: one fused jit (gather + AND + popcount +
    threshold + survivor compaction, :func:`fused_intersect_compact_ref`),
    the semantics every other backend must match bit-exactly."""

    def _expand_call(self, bitmaps, left, right, sup_left, *, mode, min_sup,
                     device_of_pair=None):
        q = int(left.shape[0])
        self.n_intersections += q
        qb, l, r, s = self.buffers.fill(left, right, sup_left)
        self._record_padding(q, qb)
        self._note_call(bitmaps, qb, mode)
        out, sup, mask_dev, _ = fused_intersect_compact_ref(
            bitmaps, _dput(l), _dput(r), _dput(s),
            _dput_i32(min_sup), _dput_i32(q), mode=mode)
        return self._survivors(out, mask_dev, sup, q)


# ---------------------------------------------------------------------------
# fused pallas backend
# ---------------------------------------------------------------------------

@register_backend("pallas")
class PallasEngine(Engine):
    """Fused executor: one pallas_call per bucket (TPU) / fused jit (CPU).

    Only the (Q,) support and mask vectors come back to the host; the
    intersection block stays on device and survivors are compacted there.
    """

    def __init__(self, bucket_min: int = 128, interpret: Optional[bool] = None,
                 *, compact_min: Optional[int] = None):
        super().__init__(bucket_min, compact_min=compact_min)
        self.interpret = interpret

    def _expand_call(self, bitmaps, left, right, sup_left, *, mode, min_sup,
                     device_of_pair=None):
        q = int(left.shape[0])
        self.n_intersections += q
        qb, l, r, s = self.buffers.fill(left, right, sup_left)
        self._record_padding(q, qb)
        self._note_call(bitmaps, qb, mode)
        inter, sup, mask_dev, _ = fused_intersect_compact(
            bitmaps, _dput(l), _dput(r), _dput(s),
            _dput_i32(min_sup), _dput_i32(q), mode=mode,
            interpret=self.interpret)
        return self._survivors(inter, mask_dev, sup, q)


# ---------------------------------------------------------------------------
# sharded backend (shard_map over either single-device executor)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _pair_executors(mesh: jax.sharding.Mesh, axis: str, inner: str,
                    interpret: Optional[bool]) -> Dict[int, Callable]:
    """Per-mode ``jit(shard_map)`` executors of the pair-sharded engine,
    built once per mesh: an engine made per mine reuses them, so nothing
    is traced or compiled again after the first mine."""
    if inner not in ("jnp", "pallas"):
        raise ValueError(f"unknown inner executor {inner!r}")

    def _local(bms, l, r, s, msup, _mode):
        if inner == "pallas":
            # the tile width is derived at trace time from the shard-local
            # shapes
            inter, sup, _ = fused_intersect(bms, l, r, s, msup, mode=_mode,
                                            interpret=interpret)
        else:
            inter, sup, _ = fused_intersect_ref(bms, l, r, s, msup,
                                                mode=_mode)
        return inter, sup

    # pallas_call has no shard_map replication rule -> unchecked variant
    smap = shard_map_unchecked if inner == "pallas" else shard_map
    return {
        mode: jax.jit(
            smap(
                lambda bms, l, r, s, m, _mode=mode: _local(bms, l, r, s, m, _mode),
                mesh=mesh,
                in_specs=(P(), P(axis), P(axis), P(axis), P()),
                out_specs=(P(axis), P(axis)),
            )
        )
        for mode in (MODE_TIDSET, MODE_TID_TO_DIFF, MODE_DIFFSET)
    }


@functools.lru_cache(maxsize=None)
def _partial_executors(mesh: jax.sharding.Mesh, data_axis: str, inner: str,
                       interpret: Optional[bool], pair_spec: P,
                       block_spec: P, compacts: bool) -> Dict[int, Callable]:
    """Per-mode ``jit(shard_map)`` executors over the partial fused kernel,
    built once per (mesh, placement): shard-local intersect + popcount, one
    psum over the word (data) axis only — class shards, if any, own
    disjoint pair blocks whose counts must never mix — then support
    conversion and the min-support mask on the reduced value.  The
    pair/block specs are the only thing the word-sharded backends differ
    by: ``P()`` / ``P(None, data)`` for ``tidsharded`` (pairs replicated),
    ``P(class)`` / ``P(class, data)`` for ``grid`` (pairs split).

    With ``compacts`` (tidsharded — its pairs are replicated, so survivor
    order is globally consistent across shards) the survivor-compaction
    epilogue runs *inside* the shard_map body: the post-psum mask is
    replicated, so every shard gathers the same survivor rows out of its
    own word slice, and the padded (Q, W) block never exists outside the
    executable.  Callers pass the true pair count ``n_valid`` as an extra
    traced operand (bucket-pad pairs must not be compacted even when their
    garbage supports pass the threshold).  That executable is jitted as
    ``fused_intersect_partial_compact``, its name in a trace."""
    if inner not in ("jnp", "pallas"):
        raise ValueError(f"unknown inner executor {inner!r}")
    spec = word_shard_spec(data_axis)

    def _local(bms, l, r, s, msup, _mode):
        if inner == "pallas":
            inter, pop = fused_intersect_partial(bms, l, r, mode=_mode,
                                                 interpret=interpret)
        else:
            inter, pop = fused_intersect_partial_ref(bms, l, r, mode=_mode)
        total = jax.lax.psum(pop, data_axis)
        sup = total if _mode == MODE_TIDSET else s - total
        mask = (sup >= msup).astype(jnp.int32)
        return inter, sup, mask

    # pallas_call has no shard_map replication rule -> unchecked variant
    smap = shard_map_unchecked if inner == "pallas" else shard_map
    if compacts:
        def executor(_mode):
            def fused_intersect_partial_compact(bms, l, r, s, msup, nv):
                inter, sup, mask = _local(bms, l, r, s, msup, _mode)
                return compact_epilogue(inter, sup, mask, nv)

            # shard_map keeps the function's name, the jitted program's
            return jax.jit(smap(
                fused_intersect_partial_compact, mesh=mesh,
                in_specs=(spec, pair_spec, pair_spec, pair_spec, P(), P()),
                out_specs=(block_spec, pair_spec, pair_spec, P())))

        return {mode: executor(mode)
                for mode in (MODE_TIDSET, MODE_TID_TO_DIFF, MODE_DIFFSET)}
    return {
        mode: jax.jit(
            smap(
                lambda bms, l, r, s, m, _mode=mode: _local(bms, l, r, s, m, _mode),
                mesh=mesh,
                in_specs=(spec, pair_spec, pair_spec, pair_spec, P()),
                out_specs=(block_spec, pair_spec, pair_spec),
            )
        )
        for mode in (MODE_TIDSET, MODE_TID_TO_DIFF, MODE_DIFFSET)
    }


@functools.lru_cache(maxsize=None)
def _sharded_take(sharding: NamedSharding) -> Callable:
    """Row gather that keeps ``sharding`` (one jitted program per placement)."""
    return jax.jit(lambda arr, idx: jax.lax.with_sharding_constraint(
        jnp.take(arr, idx, axis=0), sharding))



@register_backend("sharded")
class ShardedEngine(Engine):
    """Executor-task mapping: pairs grouped by partition device, padded per
    device to a common bucket, run under ``shard_map`` with the frontier
    replicated — the paper's communication-free executor stage."""

    def __init__(self, mesh: jax.sharding.Mesh, bucket_min: int = 128,
                 axis: str = "data", inner: str = "pallas",
                 interpret: Optional[bool] = None,
                 *, compact_min: Optional[int] = None):
        super().__init__(bucket_min, compact_min=compact_min)
        self.mesh = mesh
        self.axis = axis
        self.inner = inner
        self.interpret = interpret
        self.n_devices = int(mesh.shape[axis])
        self.block_budget = block_budget(mesh.devices.flat[0])
        # upload placements matching the executor's in_specs (see _dput)
        self._rep_sharding = NamedSharding(mesh, P())
        self._pair_sharding = NamedSharding(mesh, P(axis))
        self._sharded = _pair_executors(mesh, axis, inner, interpret)

    def _expand_call(self, bitmaps, left, right, sup_left, *, mode, min_sup,
                     device_of_pair=None):
        q = int(left.shape[0])
        self.n_intersections += q
        d = self.n_devices
        qmax, lpad, rpad, spad, slot_of_pair, counts = group_pairs_by_device(
            left, right, sup_left, device_of_pair, d, self.buffers.floor)
        self.device_pair_counts.append(counts)
        self._record_padding(q, d * qmax)
        out, sup = self._sharded[mode](
            bitmaps,
            _dput(lpad.reshape(d * qmax), self._pair_sharding),
            _dput(rpad.reshape(d * qmax), self._pair_sharding),
            _dput(spad.reshape(d * qmax), self._pair_sharding),
            _dput_i32(min_sup, self._rep_sharding),
        )
        sup_h, = self._read(sup)
        sup_np = sup_h.reshape(-1)[slot_of_pair]
        mask = sup_np >= min_sup
        sel = np.nonzero(mask)[0]
        surv = self._compact(out.reshape(d * qmax, -1),
                             slot_of_pair[sel].astype(np.int32))
        return LevelResult(mask=mask,
                           supports=sup_np[sel].astype(np.int64),
                           bitmaps=surv)


# ---------------------------------------------------------------------------
# word-axis frontier handling, shared by tidsharded + grid
# ---------------------------------------------------------------------------

class _WordShardedFrontierMixin:
    """The word-axis (tid) half of a mesh-mapped backend: carry the frontier
    as ``P(None, data_axis)`` — rows replicated over every other mesh axis,
    the packed word axis split — and keep it that way across levels.

    Owns exactly three responsibilities (the axis-ownership contract in the
    module docstring): ``_ensure_sharded`` commits/pads a frontier to the
    word sharding, ``_take`` keeps survivor row gathers under that
    constraint so next-level frontiers are *born* word-sharded, and
    ``prepare_frontier`` exposes the placement to drivers that expand one
    frontier many times (the chunked level-2 path).
    """

    def _init_word_axis(self, mesh: jax.sharding.Mesh, data_axis: str) -> None:
        self.mesh = mesh
        self.data_axis = data_axis
        self.n_shards = int(mesh.shape[data_axis])
        self.word_split = self.n_shards
        self.block_budget = block_budget(mesh.devices.flat[0])
        self._spec = word_shard_spec(data_axis)
        self._sharding = NamedSharding(mesh, self._spec)
        self._rep_sharding = NamedSharding(mesh, P())
        self._take_rows_sharded = _sharded_take(self._sharding)

    def _ensure_sharded(self, bitmaps: jax.Array) -> jax.Array:
        """Commit the frontier to ``P(None, data_axis)``, zero-padding the
        word axis to a shard multiple.  Frontiers this engine produced are
        already placed (compaction keeps the constraint), so steady-state
        levels are a no-op here."""
        if bitmaps.shape[1] % self.n_shards == 0:
            sh = getattr(bitmaps, "sharding", None)
            if (isinstance(sh, NamedSharding) and sh.mesh == self.mesh
                    and sh.spec == self._spec):
                return bitmaps
        return shard_words(bitmaps, self.mesh, self.data_axis)

    def _take(self, block: jax.Array, idx: jax.Array) -> jax.Array:
        # survivor gather under the word-sharding constraint: rows move (for
        # the grid backend, across the class axis only), the word slices stay
        # on the shard that owns them
        return self._take_rows_sharded(block, idx)

    def prepare_frontier(self, bitmaps: jax.Array) -> jax.Array:
        return self._ensure_sharded(bitmaps)


# ---------------------------------------------------------------------------
# tid-sharded backend (frontier word axis split across the mesh)
# ---------------------------------------------------------------------------

@register_backend("tidsharded")
class TidShardedEngine(_WordShardedFrontierMixin, Engine):
    """Word-sharded executor: the frontier bitmap is carried as
    ``P(None, axis)`` — rows replicated, the packed word (tid) axis split
    across the mesh — so each device stores 1/n_shards of every tidset.

    Per expansion, every shard intersects and popcounts its word slice for
    *all* pairs (the partial kernel), one ``psum`` across shards turns the
    partial counts into supports, and the min-support mask is applied to the
    reduced value.  Survivor compaction is shard-local: the prefix-sum
    compaction epilogue runs *inside* the shard_map executable — the
    post-psum mask is replicated, so every shard gathers the same survivor
    rows out of its own word slice in the same dispatch.  The full (Q, W)
    intersection block never materializes on any single device, the host,
    or the interconnect — only the (Q,) count vector crosses shards.  This
    is the mode that lets a window larger than one device's memory stay
    minable (DESIGN.md §7); trade-off vs the pair-sharded engine: every
    device does every pair's AND, but on 1/n of the words, so compute per
    device is unchanged while memory drops ~1/n.
    """

    def __init__(self, mesh: jax.sharding.Mesh, bucket_min: int = 128,
                 axis: str = "data", inner: str = "pallas",
                 interpret: Optional[bool] = None,
                 *, compact_min: Optional[int] = None):
        super().__init__(bucket_min, compact_min=compact_min)
        self.inner = inner
        self.interpret = interpret
        self._init_word_axis(mesh, axis)
        # pairs are never distributed in this mode: partition->device routing
        # (device_of_pair) is meaningless and ignored, so advertise a single
        # pair device to the drivers
        self.n_devices = 1
        # pairs are replicated, so survivor order is global: compaction runs
        # inside the executable
        self._sharded = _partial_executors(mesh, axis, inner, interpret, P(),
                                           self._spec, True)

    def stats(self, since=None) -> dict:
        out = super().stats(since=since)
        out["n_word_shards"] = self.n_shards
        return out

    def _expand_call(self, bitmaps, left, right, sup_left, *, mode, min_sup,
                     device_of_pair=None):
        q = int(left.shape[0])
        self.n_intersections += q
        qb, l, r, s = self.buffers.fill(left, right, sup_left)
        self._record_padding(q, qb)
        self._note_call(bitmaps, qb, mode)
        bitmaps = self._ensure_sharded(bitmaps)
        rep = self._rep_sharding
        self.n_psums += 1
        inter, sup, mask_dev, _ = self._sharded[mode](
            bitmaps, _dput(l, rep), _dput(r, rep), _dput(s, rep),
            _dput_i32(min_sup, rep), _dput_i32(q, rep))
        res = self._survivors(inter, mask_dev, sup, q)
        res.bitmaps = jax.device_put(res.bitmaps, self._sharding)
        return res


# ---------------------------------------------------------------------------
# grid-sharded backend (pairs x words on a 2D mesh)
# ---------------------------------------------------------------------------

@register_backend("grid")
class GridShardedEngine(_WordShardedFrontierMixin, Engine):
    """Grid-sharded executor on a 2D ``("class", "data")`` mesh: the pair
    list is split over the **class** axis (grouped by partitioned
    equivalence class, exactly as in :class:`ShardedEngine`) while the
    frontier's packed word (tid) axis is split over the **data** axis
    (exactly as in :class:`TidShardedEngine`).  The frontier is carried as
    ``P(None, "data")`` — replicated over ``"class"``, word-sharded over
    ``"data"`` — so each of the ``n_class * n_data`` devices executes the
    partial fused kernel on one (class-shard pairs) x (word-shard words)
    tile.

    Supports are recovered with one ``psum`` over the **data axis only**:
    the class shards own disjoint pair blocks, so their counts must never
    mix — after the reduce, every device in a data row holds the finished
    supports of its class shard's pairs.  Survivor compaction gathers rows
    under the ``P(None, "data")`` constraint: word slices never cross the
    data axis; survivor rows are replicated over the class axis only (the
    same survivor broadcast the pair-sharded engine performs implicitly),
    so the next level's frontier is born grid-placed.

    Net effect vs the 1D modes (DESIGN.md §8): per-device pair work drops
    ~1/n_class (vs ``tidsharded``, which replicates all pairs) AND
    per-device frontier memory drops ~1/n_data (vs ``sharded``, which
    replicates the whole frontier) — the two scaling axes the paper treats
    separately (executor count, database size), composed on one mesh.
    """

    def __init__(self, mesh: jax.sharding.Mesh, bucket_min: int = 128,
                 class_axis: str = "class", data_axis: str = "data",
                 inner: str = "pallas", interpret: Optional[bool] = None,
                 *, compact_min: Optional[int] = None):
        super().__init__(bucket_min, compact_min=compact_min)
        missing = [a for a in (class_axis, data_axis)
                   if a not in mesh.axis_names]
        if missing:
            raise ValueError(
                f"grid backend needs a 2D ({class_axis!r}, {data_axis!r}) "
                f"mesh (launch.mesh.make_grid_mesh); this mesh has axes "
                f"{tuple(mesh.axis_names)}")
        self.class_axis = class_axis
        self.inner = inner
        self.interpret = interpret
        self._init_word_axis(mesh, data_axis)
        self.n_class = int(mesh.shape[class_axis])
        # drivers route partition->device over the pair (class) axis
        self.n_devices = self.n_class
        self._pair_vec_sharding = NamedSharding(mesh, grid_pair_spec(class_axis))
        # survivors live in per-class pad blocks whose order differs from
        # global pair order, so in-executable compaction would emit them
        # class-blocked: they are gathered after the read (Engine._compact)
        self._sharded = _partial_executors(
            mesh, data_axis, inner, interpret, grid_pair_spec(class_axis),
            grid_block_spec(class_axis, data_axis), False)

    def stats(self, since=None) -> dict:
        out = super().stats(since=since)
        out["n_class_shards"] = self.n_class
        out["n_word_shards"] = self.n_shards
        out["grid"] = [self.n_class, self.n_shards]
        return out

    def _expand_call(self, bitmaps, left, right, sup_left, *, mode, min_sup,
                     device_of_pair=None):
        q = int(left.shape[0])
        self.n_intersections += q
        d = self.n_class
        qmax, lpad, rpad, spad, slot_of_pair, counts = group_pairs_by_device(
            left, right, sup_left, device_of_pair, d, self.buffers.floor)
        self.device_pair_counts.append(counts)
        self._record_padding(q, d * qmax)
        bitmaps = self._ensure_sharded(bitmaps)
        self.n_psums += 1
        inter, sup, mask_dev = self._sharded[mode](
            bitmaps,
            _dput(lpad.reshape(d * qmax), self._pair_vec_sharding),
            _dput(rpad.reshape(d * qmax), self._pair_vec_sharding),
            _dput(spad.reshape(d * qmax), self._pair_vec_sharding),
            _dput_i32(min_sup, self._rep_sharding),
        )
        sup_h, mask_h = self._read(sup, mask_dev)
        sup_np = sup_h.reshape(-1)[slot_of_pair]
        mask = mask_h.reshape(-1)[slot_of_pair].astype(bool)
        sel = np.nonzero(mask)[0]
        surv = self._compact(inter, slot_of_pair[sel].astype(np.int32))
        return LevelResult(mask=mask,
                           supports=sup_np[sel].astype(np.int64),
                           bitmaps=surv)
