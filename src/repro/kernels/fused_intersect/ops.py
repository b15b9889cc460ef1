"""Dispatching wrapper: the fused Pallas kernel on TPU, its jnp twin elsewhere.

``repro.core.engine`` routes the pallas backend's pair batches through here.
Which executable runs is decided once per call by :func:`kernel_path` and
reported by the engine's ``stats()["kernel_path"]``, never silently:

  ``mosaic``     the Pallas kernel compiled by Mosaic — the TPU path;
  ``interpret``  the same kernel under the Pallas interpreter (tests);
  ``xla-ref``    the identically-fused jnp computation (``ref.py``), which
                 is what a CPU host runs when no interpreter is requested.

``block_w`` resolution: ``None`` (the default everywhere above this layer)
is derived at trace time by :func:`resolve_block_w` from the call's shape
and the cost model, so every call site — including the shard_map-wrapped
partial kernels, whose bodies trace through here — gets the same width
without threading one through every driver.  An explicit ``block_w``
(kernel-level tests, compiles for a described chip) wins.
"""
from __future__ import annotations

from typing import List, Optional

import jax

from ...analysis.roofline import intersect_cost
from .fused_intersect import (DEFAULT_BLOCK_W, fused_intersect_compact_pairs,
                              fused_intersect_pairs,
                              fused_intersect_partial_pairs, round_up_lanes)
from .ref import (fused_intersect_compact_ref, fused_intersect_partial_ref,
                  fused_intersect_ref)


def kernel_path(interpret: Optional[bool] = None) -> str:
    """The executable a call with this ``interpret`` flag runs on the
    current default backend: ``mosaic``, ``interpret`` or ``xla-ref``."""
    if interpret:
        return "interpret"
    if interpret is None and jax.default_backend() != "tpu":
        return "xla-ref"
    return "mosaic"


# candidate tile widths: every lane-aligned power of two the pipeline can
# reasonably hold double-buffered in VMEM ((1, bw) uint32 blocks x 2 rows
# x 2 buffers -> 8 KiB/lane-k at bw=2048)
_POW2_CANDIDATES = (128, 256, 512, 1024, 2048)


def _kind() -> str:
    return "tpu" if jax.default_backend() == "tpu" else "xla"


def block_w_candidates(w: int, kind: Optional[str] = None) -> List[int]:
    """Lane-aligned candidate tile widths for a row of ``w`` words: the
    power-of-two ladder capped at the lane-padded row width, plus the
    padded width itself (the single-block tile).  Off-TPU (``kind="xla"``)
    the fused XLA path has no tile parameter, so the list collapses to one
    width."""
    kind = _kind() if kind is None else kind
    wp = round_up_lanes(w)
    if kind == "xla":
        return [min(DEFAULT_BLOCK_W, wp)]
    return sorted({c for c in _POW2_CANDIDATES if c <= wp} | {wp})


def seeded_candidates(q: int, w: int,
                      kind: Optional[str] = None) -> List[int]:
    """Candidates ordered by the roofline cost model, best predicted first:
    ``intersect_cost`` charges per-block-step overhead (penalizing tiny
    tiles) and padded-word streaming (penalizing over-wide tiles on narrow
    rows)."""
    return sorted(block_w_candidates(w, kind),
                  key=lambda bw: intersect_cost(q, w, bw).bound_s)


def resolve_block_w(block_w, q: int, w: int, mode: int,
                    kind: Optional[str] = None) -> int:
    """The word-tile width of a ``q``-pair call over ``w``-word rows: an
    explicit ``block_w`` if given, else the cost model's pick among the
    candidates (``kind`` ``"tpu"`` on a TPU, ``"xla"`` elsewhere, unless
    given).  Derived, never measured: the same shape always compiles the
    same tile, in every mode."""
    if block_w is not None:
        return int(block_w)
    return seeded_candidates(q, w, kind)[0]


def fused_intersect_partial(
    bitmaps: jax.Array,
    left: jax.Array,
    right: jax.Array,
    *,
    mode: int,
    block_w: int | None = None,
    interpret: bool | None = None,
):
    """Shard-local fused gather+AND+popcount (no threshold); see the partial
    kernel docstring.  Dispatch mirrors :func:`fused_intersect`."""
    if kernel_path(interpret) == "xla-ref":
        return fused_intersect_partial_ref(bitmaps, left, right, mode=mode)
    bw = resolve_block_w(block_w, left.shape[0], bitmaps.shape[1], mode)
    return fused_intersect_partial_pairs(bitmaps, left, right, mode=mode,
                                         block_w=bw, interpret=bool(interpret))


def fused_intersect(
    bitmaps: jax.Array,
    left: jax.Array,
    right: jax.Array,
    sup_left: jax.Array,
    min_sup,
    *,
    mode: int,
    block_w: int | None = None,
    interpret: bool | None = None,
):
    """Fused gather+AND+popcount+mask.  See kernel docstring for tiling."""
    if kernel_path(interpret) == "xla-ref":
        return fused_intersect_ref(bitmaps, left, right, sup_left, min_sup,
                                   mode=mode)
    bw = resolve_block_w(block_w, left.shape[0], bitmaps.shape[1], mode)
    return fused_intersect_pairs(bitmaps, left, right, sup_left, min_sup,
                                 mode=mode, block_w=bw,
                                 interpret=bool(interpret))


def fused_intersect_compact(
    bitmaps: jax.Array,
    left: jax.Array,
    right: jax.Array,
    sup_left: jax.Array,
    min_sup,
    n_valid,
    *,
    mode: int,
    block_w: int | None = None,
    interpret: bool | None = None,
):
    """Fused gather+AND+popcount+mask with the survivor-compaction epilogue
    in the same executable: returns ``(compact, sup, mask, n_surv)`` —
    ``compact[:n_surv]`` are the surviving rows in ascending pair order
    (pairs >= ``n_valid`` are bucket padding and excluded).  Dispatch
    mirrors :func:`fused_intersect`."""
    if kernel_path(interpret) == "xla-ref":
        return fused_intersect_compact_ref(bitmaps, left, right, sup_left,
                                           min_sup, n_valid, mode=mode)
    bw = resolve_block_w(block_w, left.shape[0], bitmaps.shape[1], mode)
    return fused_intersect_compact_pairs(
        bitmaps, left, right, sup_left, min_sup, n_valid,
        mode=mode, block_w=bw, interpret=bool(interpret))
