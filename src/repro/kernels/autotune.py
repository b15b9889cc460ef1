"""Autotuned tile configs for the fused-intersect kernel.

``DEFAULT_BLOCK_W = 512`` was a guess; the right tile width for the
gather+AND+popcount loop depends on the frontier width (how many word
blocks a row spans), the pair count (how much pipeline there is to fill),
and the backend actually executing (Mosaic kernel on TPU, fused XLA
elsewhere).  This module makes it a measured decision:

1.  **Shape classes.**  Expansions are bucketed by the same power-of-two
    ladders the engine already pads to (``q`` rung, ``w`` rung, mode,
    executing backend), so one tuned entry covers every call that compiles
    to the same executable.
2.  **Cost-model seeding.**  Candidate widths are lane-aligned
    (128-multiples) and *ordered* by ``analysis.roofline.intersect_cost``
    — the compute-vs-HBM model of the loop — so measurement starts from
    the predicted winner and the sweep can be truncated without losing it.
3.  **Measurement, then cache.**  Each candidate is timed steady-state
    (compile excluded, ``block_until_ready`` inside the timed region) on
    synthetic data of the class shape; the winner lands in a JSON table
    keyed by shape class.  A table on disk is read only when asked for:
    ``REPRO_AUTOTUNE_CACHE`` names one, or ``--autotune``
    (:func:`enable_user_cache`) opts into ``~/.cache/repro-eclat/
    autotune.json``.  Otherwise the table lives in memory, so nothing
    outside the checkout changes which block width a run compiles.
4.  **Lookup at trace time.**  ``repro.kernels.fused_intersect.ops``
    resolves ``block_w=None`` through :func:`lookup`; the table read is a
    host-side dict hit during tracing, so tuned widths reach every backend
    — including the shard_map-wrapped partial kernels — with zero traced
    overhead.

Off-TPU (this CPU container) the non-interpret fused path is the XLA ref,
which has no tile parameter — ``candidates`` collapses to the single
lane-padded width and the measured decision reduces to the in-executable
compaction on/off choice the engine exposes.  The sweep still runs under
``interpret=True`` in tests to pin the mechanics.
"""
from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from ..analysis.roofline import intersect_cost
from .fused_intersect.fused_intersect import (DEFAULT_BLOCK_W, MODE_TIDSET,
                                              round_up_lanes)

__all__ = ["KernelConfig", "shape_class", "block_w_candidates",
           "seeded_candidates", "AutotuneTable", "table_path", "load_table",
           "lookup", "tune_shape", "reset", "enable_user_cache",
           "DEFAULT_BLOCK_W"]

CACHE_ENV = "REPRO_AUTOTUNE_CACHE"
_DEFAULT_CACHE = os.path.join("~", ".cache", "repro-eclat", "autotune.json")

# candidate tile widths: every lane-aligned power of two the pipeline can
# reasonably hold double-buffered in VMEM ((1, bw) uint32 blocks x 2 rows
# x 2 buffers -> 8 KiB/lane-k at bw=2048)
_POW2_CANDIDATES = (128, 256, 512, 1024, 2048)


@dataclasses.dataclass(frozen=True)
class KernelConfig:
    """One tuned kernel configuration for a shape class.

    ``block_w``: word-tile width of the fused kernel (lane-aligned).
    ``compact``: run the survivor-compaction epilogue inside the fused
    executable (one dispatch) instead of the legacy mask-roundtrip +
    separate gather (two dispatches).
    """

    block_w: int = DEFAULT_BLOCK_W
    compact: bool = True

    def to_dict(self) -> dict:
        return {"block_w": int(self.block_w), "compact": bool(self.compact)}

    @classmethod
    def from_dict(cls, d: dict) -> "KernelConfig":
        return cls(block_w=int(d.get("block_w", DEFAULT_BLOCK_W)),
                   compact=bool(d.get("compact", True)))


def _pow2_bucket(n: int) -> int:
    b = 1
    while b < max(int(n), 1):
        b <<= 1
    return b


def shape_class(q: int, w: int, mode: int = MODE_TIDSET,
                kind: Optional[str] = None) -> str:
    """Stable key for 'calls that hit the same executable': power-of-two
    rungs of the pair count and the lane-padded word width, the intersect
    mode, and the executing path (``tpu`` Mosaic / ``xla`` fused ref /
    ``interpret``)."""
    if kind is None:
        kind = "tpu" if jax.default_backend() == "tpu" else "xla"
    return (f"q{_pow2_bucket(q)}_w{_pow2_bucket(round_up_lanes(w))}"
            f"_m{int(mode)}_{kind}")


def block_w_candidates(w: int, kind: Optional[str] = None) -> List[int]:
    """Lane-aligned candidate tile widths for a row of ``w`` words: the
    power-of-two ladder capped at the lane-padded row width, plus the
    padded width itself (the single-block tile).  Off-TPU the fused XLA
    path has no tile parameter, so the list collapses to the one padded
    width — a tuner must not pretend to sweep a knob the executable does
    not have."""
    if kind is None:
        kind = "tpu" if jax.default_backend() == "tpu" else "xla"
    wp = round_up_lanes(w)
    if kind == "xla":
        return [min(DEFAULT_BLOCK_W, wp)]
    cands = sorted({c for c in _POW2_CANDIDATES if c <= wp} | {wp})
    return cands


def seeded_candidates(q: int, w: int,
                      kind: Optional[str] = None) -> List[int]:
    """Candidates ordered by the roofline cost model (best predicted
    first): ``intersect_cost`` charges per-block-step overhead (penalizing
    tiny tiles) and padded-word streaming (penalizing over-wide tiles on
    narrow rows), so the predicted winner leads the measured sweep."""
    cands = block_w_candidates(w, kind)
    return sorted(cands, key=lambda bw: intersect_cost(q, w, bw).bound_s)


# ---------------------------------------------------------------------------
# persistent shape -> config table
# ---------------------------------------------------------------------------

class AutotuneTable:
    """Shape-class -> :class:`KernelConfig` map with JSON persistence.

    Entries carry provenance (``source``: measured / seeded / manual) and
    the measured steady-state seconds, so a bench artifact can report not
    just the winner but the margin."""

    def __init__(self, path: Optional[str] = None):
        self.path = path
        self.entries: Dict[str, dict] = {}

    def get(self, key: str) -> Optional[KernelConfig]:
        e = self.entries.get(key)
        return KernelConfig.from_dict(e) if e is not None else None

    def put(self, key: str, config: KernelConfig, *,
            measured_s: Optional[float] = None,
            source: str = "measured") -> None:
        self.entries[key] = {**config.to_dict(), "source": source}
        if measured_s is not None:
            self.entries[key]["measured_s"] = float(measured_s)

    def load(self) -> "AutotuneTable":
        if self.path and os.path.exists(self.path):
            try:
                with open(self.path) as f:
                    data = json.load(f)
                self.entries.update(data.get("shapes", {}))
            except (OSError, ValueError):
                pass  # a corrupt cache is a cache miss, not a crash
        return self

    def save(self) -> None:
        if not self.path:
            return
        os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)
        tmp = self.path + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"version": 1, "shapes": self.entries}, f, indent=2,
                      sort_keys=True)
        os.replace(tmp, self.path)


_USE_USER_CACHE = False


def enable_user_cache() -> None:
    """Opt into the per-user table on disk (``--autotune``)."""
    global _USE_USER_CACHE
    if not _USE_USER_CACHE:
        _USE_USER_CACHE = True
        reset()


def table_path() -> Optional[str]:
    """``REPRO_AUTOTUNE_CACHE`` if set; else the per-user table once
    :func:`enable_user_cache` opted in; else ``None`` (in memory only)."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return os.path.expanduser(env)
    return os.path.expanduser(_DEFAULT_CACHE) if _USE_USER_CACHE else None


_TABLE: Optional[AutotuneTable] = None


def load_table(refresh: bool = False) -> AutotuneTable:
    """The process-wide table, loaded once from :func:`table_path`."""
    global _TABLE
    if _TABLE is None or refresh:
        _TABLE = AutotuneTable(table_path()).load()
    return _TABLE


def reset() -> None:
    """Drop the cached in-process table (tests; after env changes)."""
    global _TABLE
    _TABLE = None


def lookup(q: int, w: int, mode: int = MODE_TIDSET,
           kind: Optional[str] = None) -> KernelConfig:
    """Tuned config for a call shape; falls back to the cost-model seed
    (best predicted candidate) when the shape was never measured.  This is
    the trace-time hook behind ``ops.fused_intersect(block_w=None)``."""
    cfg = load_table().get(shape_class(q, w, mode, kind))
    if cfg is not None:
        return cfg
    return KernelConfig(block_w=seeded_candidates(q, w, kind)[0])


# ---------------------------------------------------------------------------
# measurement
# ---------------------------------------------------------------------------

def measure_steady(fn: Callable[[], jax.Array], reps: int = 5,
                   warmup: int = 1) -> Tuple[float, float]:
    """(compile_s, steady_s): first call timed separately (trace+compile),
    then ``reps`` calls each blocked to completion inside the timed region
    — the timing-hygiene contract every benchmark in this repo follows."""
    t0 = time.perf_counter()
    jax.block_until_ready(fn())
    compile_s = time.perf_counter() - t0
    for _ in range(max(warmup - 1, 0)):
        jax.block_until_ready(fn())
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn())
    return compile_s, (time.perf_counter() - t0) / reps


def _synthetic_case(q: int, w: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    p = max(min(int(q), 4096), 2)
    bitmaps = jnp.asarray(rng.integers(0, 2 ** 32, (p, w), dtype=np.uint32))
    left = jnp.asarray(rng.integers(0, p, q).astype(np.int32))
    right = jnp.asarray(rng.integers(0, p, q).astype(np.int32))
    supl = jnp.asarray(np.full(q, w * 32, np.int32))
    return bitmaps, left, right, supl


def tune_shape(q: int, w: int, mode: int = MODE_TIDSET, *,
               kind: Optional[str] = None,
               reps: int = 5,
               max_candidates: Optional[int] = None,
               interpret: bool = False,
               save: bool = True) -> dict:
    """Measure the seeded candidates for one (q, w, mode) shape class and
    cache the winner.

    Returns the bench record: per-candidate steady seconds, the tuned
    ``block_w``, the cost-model's pick, and whether they agree.  With
    ``max_candidates`` the sweep keeps only the model's top-N — the seeding
    is what makes truncation safe.
    """
    from .fused_intersect.fused_intersect import fused_intersect_pairs
    from .fused_intersect.ref import fused_intersect_ref

    if kind is None:
        kind = ("interpret" if interpret
                else "tpu" if jax.default_backend() == "tpu" else "xla")
    cands = seeded_candidates(q, w, "xla" if kind == "xla" else "tpu")
    if max_candidates is not None:
        cands = cands[:max_candidates]
    bitmaps, left, right, supl = _synthetic_case(q, w)
    msup = jnp.int32(w * 16)

    timings: Dict[int, float] = {}
    compiles: Dict[int, float] = {}
    for bw in cands:
        if kind == "xla":
            fn = lambda: fused_intersect_ref(
                bitmaps, left, right, supl, msup, mode=mode)[1]
        else:
            fn = lambda bw=bw: fused_intersect_pairs(
                bitmaps, left, right, supl, msup, mode=mode, block_w=bw,
                interpret=(kind == "interpret"))[1]
        compile_s, steady_s = measure_steady(fn, reps=reps)
        timings[bw] = steady_s
        compiles[bw] = compile_s
    best = min(timings, key=timings.get)
    config = KernelConfig(block_w=best)
    key = shape_class(q, w, mode, "xla" if kind == "xla" else "tpu")
    table = load_table()
    table.put(key, config, measured_s=timings[best], source="measured")
    if save:
        table.save()
    return {
        "key": key, "q": int(q), "w": int(w), "mode": int(mode),
        "kind": kind,
        "candidates": {str(bw): timings[bw] for bw in cands},
        "compile_s": {str(bw): compiles[bw] for bw in cands},
        "tuned_block_w": int(best),
        "model_pick": int(cands[0]),
        "model_agrees": bool(best == cands[0]),
        "steady_s": timings[best],
        "default_steady_s": timings.get(
            min(DEFAULT_BLOCK_W, round_up_lanes(w)), timings[best]),
    }
