"""Pallas TPU kernel: fused gather + AND + popcount for candidate pairs.

The Eclat hot loop in one ``pallas_call``: for each candidate pair ``q`` the
kernel DMA-gathers the two parent bitmap rows straight out of the frontier
(no materialized ``jnp.take`` copies), intersects them in the mode the miner
is running in, and accumulates the row popcount across the word grid.  The
jitted wrappers turn the popcount into a support and compare it against
``min_sup`` in the same executable, so only the ``(Q,)`` support and mask
vectors need to cross back to the driver; the ``(Q, W)`` intersection stays
device-resident for the survivor compaction.

Modes (match ``repro.core.engine``):
    0  tidset:           inter = a & b,   sup = |inter|
    1  tidset->diffset:  inter = a & ~b,  sup = sup_left - |inter|
    2  diffset:          inter = b & ~a,  sup = sup_left - |inter|

Block shapes are the ones Mosaic accepts (the TPU compile tests in
``tests/test_tpu_compile.py`` hold every variant to them):

* **Row gather through a ``(P, 1, W)`` view.**  A ``(1, bw)`` block of a
  ``(P, W)`` array is refused (its second-minor dim is neither 8-aligned nor
  the full dim).  Viewing the frontier as ``(P, 1, W)`` makes the block
  ``(None, 1, bw)``: the squeezed leading dim is the gathered row, picked by
  the scalar-prefetched pair indices (``PrefetchScalarGridSpec``), and the
  trailing ``(1, bw)`` is a full-extent tile.  The grid is ``(Q, W/bw)`` with
  the word axis innermost; the pipeline double-buffers each operand, so the
  gather of step ``(q, j+1)`` overlaps the AND+popcount of ``(q, j)``.
* **Lane-dense per-pair output.**  The popcount of pair ``q`` is written to
  lane ``q % 128`` of a ``(1, 128)`` block that stays resident for 128
  consecutive pairs, so the output is ``(ceil(Q/128), 1, 128)`` int32 and no
  ``(1,)`` VMEM block or scalar store exists.  Revisiting that block makes
  the pair axis ``arbitrary`` (sequential), which costs nothing on a
  one-TensorCore v5e.
* **SMEM budget.**  The ``(2, Q)`` int32 pair indices are the only
  scalar-prefetch operand: 8 bytes per pair of the 1 MiB SMEM.
  :data:`MAX_PAIRS_PER_CALL` is the per-call cap the engine never exceeds.

``block_w`` is resolved by the ``ops`` dispatch layer from the cost model
(``ops.resolve_block_w``); ``DEFAULT_BLOCK_W`` is the fallback value only.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_W = 512
LANE = 128                      # VPU lane width: all block widths are 128-multiples

SMEM_BYTES = 1 << 20            # scalar memory of one TPU v5e core
IDX_BYTES_PER_PAIR = 8          # (2, Q) int32 scalar-prefetched pair indices
# Largest pair batch one kernel call may take: a power of two whose index
# prefetch fills at most half of SMEM, leaving the rest to Mosaic's own
# scalars.  2**16 pairs -> 512 KiB; 2**18 pairs (the old level-2 chunk) asked
# for 2 MiB and was refused by the compiler.
MAX_PAIRS_PER_CALL = 1 << ((SMEM_BYTES // 2 // IDX_BYTES_PER_PAIR).bit_length() - 1)

MODE_TIDSET = 0
MODE_TID_TO_DIFF = 1
MODE_DIFFSET = 2


def round_up_lanes(n: int) -> int:
    """Smallest 128-multiple >= n (>= 128): the lane-aligned word width."""
    return max((int(n) + LANE - 1) // LANE * LANE, LANE)


def _resolve_block_w(w: int, block_w: int) -> int:
    """Lane-align a requested tile width and cap it at the (lane-padded)
    row width — a wider block than the row would only stream zeros."""
    return min(round_up_lanes(block_w), round_up_lanes(w))


def _intersect(a, b, mode):
    if mode == MODE_TIDSET:
        return jnp.bitwise_and(a, b)
    if mode == MODE_TID_TO_DIFF:
        return jnp.bitwise_and(a, jnp.bitwise_not(b))
    return jnp.bitwise_and(b, jnp.bitwise_not(a))


def _kernel(idx_ref, a_ref, b_ref, inter_ref, pop_ref, acc_ref, *, mode):
    """One (pair, word block) grid step: intersect, store, accumulate the
    per-word popcount; on the last word block fold it into the pair's lane
    of the lane-dense popcount output."""
    q = pl.program_id(0)
    wj = pl.program_id(1)
    inter = _intersect(a_ref[...], b_ref[...], mode)
    inter_ref[...] = inter
    pc = jax.lax.population_count(inter).astype(jnp.int32)

    @pl.when(wj == 0)
    def _init():
        acc_ref[...] = pc

    @pl.when(wj != 0)
    def _acc():
        acc_ref[...] = acc_ref[...] + pc

    @pl.when(wj == pl.num_programs(1) - 1)
    def _finish():
        total = jnp.sum(acc_ref[...], axis=1, keepdims=True)
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANE), 1)
        pop_ref[...] = jnp.where(lane == q % LANE, total, pop_ref[...])


def _pad_words(bitmaps: jax.Array, bw: int) -> jax.Array:
    pad_w = (-bitmaps.shape[1]) % bw
    if pad_w:
        bitmaps = jnp.pad(bitmaps, ((0, 0), (0, pad_w)))
    return bitmaps


def _popcount_pairs(bitmaps, left, right, *, mode, block_w, interpret):
    """Shared kernel launch: validate, lane-pad, gather-intersect-count.
    Returns the *word-padded* ``(Q, Wp)`` intersection block, the ``(Q,)``
    int32 popcounts and the unpadded width ``w``."""
    if bitmaps.ndim != 2:
        raise ValueError(f"expected (P, W) frontier, got {bitmaps.shape}")
    if left.shape != right.shape or left.ndim != 1:
        raise ValueError("left/right must share a (Q,) shape")
    qn = left.shape[0]
    if qn > MAX_PAIRS_PER_CALL:
        raise ValueError(f"{qn} pairs exceed the per-call cap "
                         f"MAX_PAIRS_PER_CALL={MAX_PAIRS_PER_CALL}")
    w = bitmaps.shape[1]
    bw = _resolve_block_w(w, block_w)
    frontier = _pad_words(bitmaps, bw)
    wp = frontier.shape[1]
    rows = frontier.reshape(frontier.shape[0], 1, wp)
    idx = jnp.stack([left.astype(jnp.int32), right.astype(jnp.int32)])
    nqb = pl.cdiv(qn, LANE)

    def row_spec(side):
        return pl.BlockSpec((None, 1, bw),
                            lambda q, j, idx_ref: (idx_ref[side, q], 0, j))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(qn, wp // bw),
        in_specs=[row_spec(0), row_spec(1)],
        out_specs=[
            pl.BlockSpec((None, 1, bw), lambda q, j, *_: (q, 0, j)),
            pl.BlockSpec((None, 1, LANE), lambda q, j, *_: (q // LANE, 0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((1, bw), jnp.int32)],
    )
    inter, pop = pl.pallas_call(
        functools.partial(_kernel, mode=mode),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((qn, 1, wp), jnp.uint32),
            jax.ShapeDtypeStruct((nqb, 1, LANE), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ) if not interpret else None,
        interpret=interpret,
        name="fused_intersect",
    )(idx, rows, rows)
    return inter.reshape(qn, wp), pop.reshape(nqb * LANE)[:qn], w


def _support_mask(pop, sup_left, min_sup, mode):
    """Popcount -> support (tidset: the count; diffset modes: the parent's
    support minus it) -> min-support mask."""
    sup = pop if mode == MODE_TIDSET else sup_left.astype(jnp.int32) - pop
    mask = (sup >= jnp.asarray(min_sup, jnp.int32)).astype(jnp.int32)
    return sup, mask


@functools.partial(
    jax.jit, static_argnames=("mode", "block_w", "interpret")
)
def fused_intersect_partial_pairs(
    bitmaps: jax.Array,
    left: jax.Array,
    right: jax.Array,
    *,
    mode: int = MODE_TIDSET,
    block_w: int = DEFAULT_BLOCK_W,
    interpret: bool = False,
):
    """(P, W) uint32 frontier shard x (Q,) int32 pair indices ->
    ((Q, W) uint32 intersections, (Q,) int32 partial popcounts).

    The word-sharded counterpart of :func:`fused_intersect_pairs`: it stops
    at the raw popcount (no support conversion, no threshold) because both
    need the *total* count, which only exists after a cross-shard psum
    (``repro.core.engine.TidShardedEngine``, DESIGN.md §7).
    """
    inter, pop, w = _popcount_pairs(bitmaps, left, right, mode=mode,
                                    block_w=block_w, interpret=interpret)
    return inter[:, :w], pop


def _check_sup_left(left, sup_left):
    if left.shape != sup_left.shape:
        raise ValueError("left/right/sup_left must share a (Q,) shape")


@functools.partial(
    jax.jit, static_argnames=("mode", "block_w", "interpret")
)
def fused_intersect_pairs(
    bitmaps: jax.Array,
    left: jax.Array,
    right: jax.Array,
    sup_left: jax.Array,
    min_sup: jax.Array | int,
    *,
    mode: int = MODE_TIDSET,
    block_w: int = DEFAULT_BLOCK_W,
    interpret: bool = False,
):
    """(P, W) uint32 frontier x (Q,) int32 pair indices ->
    ((Q, W) uint32 intersections, (Q,) int32 supports, (Q,) int32 mask).

    ``min_sup`` is a traced operand, so sweeping the threshold does not
    recompile; only ``mode`` and the block shape do.  W need not be a
    multiple of ``block_w``; the frontier is zero-padded (zero words
    contribute zero popcount).
    """
    _check_sup_left(left, sup_left)
    inter, pop, w = _popcount_pairs(bitmaps, left, right, mode=mode,
                                    block_w=block_w, interpret=interpret)
    sup, mask = _support_mask(pop, sup_left, min_sup, mode)
    return inter[:, :w], sup, mask


def compact_epilogue(inter: jax.Array, sup: jax.Array, mask: jax.Array,
                     n_valid: jax.Array | int):
    """Fold the min-sup mask + a prefix-sum survivor scatter into the fused
    executable: ``(Q, Wp)`` intersections + ``(Q,)`` mask -> ``(Q, Wp)``
    block whose rows ``[:S]`` are the survivors in ascending pair order
    (rows ``[S:]`` duplicate row 0 — the engine's rung-padding convention)
    plus the survivor count ``S``.

    ``n_valid`` masks out the bucket-ladder pad pairs (a padded ``(0, 0)``
    self-pair can clear any threshold), traced so the valid count never
    recompiles.  ``jnp.nonzero(size=Q)`` *is* the prefix-sum scatter:
    XLA lowers it to cumsum + scatter with a static output shape, so the
    whole mask->compact path stays inside one dispatch and the full block
    never needs a host round-trip before compaction.
    """
    q = mask.shape[0]
    valid = jnp.arange(q, dtype=jnp.int32) < jnp.asarray(n_valid, jnp.int32)
    m = (mask != 0) & valid
    sel = jnp.nonzero(m, size=q, fill_value=0)[0]
    compact = jnp.take(inter, sel, axis=0)
    return compact, sup, m.astype(jnp.int32), m.sum(dtype=jnp.int32)


@functools.partial(
    jax.jit, static_argnames=("mode", "block_w", "interpret")
)
def fused_intersect_compact_pairs(
    bitmaps: jax.Array,
    left: jax.Array,
    right: jax.Array,
    sup_left: jax.Array,
    min_sup: jax.Array | int,
    n_valid: jax.Array | int,
    *,
    mode: int = MODE_TIDSET,
    block_w: int = DEFAULT_BLOCK_W,
    interpret: bool = False,
):
    """:func:`fused_intersect_pairs` with in-executable survivor compaction:
    one dispatch returns ``(compact (Q, W), sup (Q,), mask (Q,), n_surv)``
    where ``compact[:n_surv]`` are the surviving intersections in ascending
    pair order.  Pairs at positions >= ``n_valid`` are bucket padding and
    never survive.  The engine reads the mask once and slices the compacted
    block to its survivor rung — no second gather dispatch, no index upload
    (DESIGN.md §3)."""
    _check_sup_left(left, sup_left)
    inter, pop, w = _popcount_pairs(bitmaps, left, right, mode=mode,
                                    block_w=block_w, interpret=interpret)
    sup, mask = _support_mask(pop, sup_left, min_sup, mode)
    compact, sup, mask, n_surv = compact_epilogue(inter, sup, mask, n_valid)
    return compact[:, :w], sup, mask, n_surv
