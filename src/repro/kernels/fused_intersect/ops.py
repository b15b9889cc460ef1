"""Dispatching wrapper: the fused Pallas kernel on TPU, its jnp twin elsewhere.

``repro.core.engine`` routes the pallas backend's pair batches through here.
Which executable runs is decided once per call by :func:`kernel_path` and
reported by the engine's ``stats()["kernel_path"]``, never silently:

  ``mosaic``     the Pallas kernel compiled by Mosaic — the TPU path;
  ``interpret``  the same kernel under the Pallas interpreter (tests);
  ``xla-ref``    the identically-fused jnp computation (``ref.py``), which
                 is what a CPU host runs when no interpreter is requested.

``block_w`` resolution: ``None`` (the default everywhere above this layer)
consults the autotuned shape->config table (``repro.kernels.autotune``) at
trace time, so tuned tile widths reach every call site — including the
shard_map-wrapped partial kernels, whose bodies trace through here — without
threading a width through every driver.  An explicit ``block_w`` (config /
CLI override) wins over the table.
"""
from __future__ import annotations

from typing import Optional

import jax

from .fused_intersect import (fused_intersect_compact_pairs,
                              fused_intersect_pairs,
                              fused_intersect_partial_pairs)
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


def resolve_block_w(block_w, q: int, w: int, mode: int) -> int:
    """Explicit width if given, else the autotuned (or cost-model-seeded)
    width for this call's shape class."""
    if block_w is not None:
        return int(block_w)
    from .. import autotune
    return autotune.lookup(q, w, mode).block_w


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
