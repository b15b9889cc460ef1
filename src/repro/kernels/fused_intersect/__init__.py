from .fused_intersect import (DEFAULT_BLOCK_W, LANE, MAX_PAIRS_PER_CALL,
                              MODE_DIFFSET, MODE_TID_TO_DIFF, MODE_TIDSET,
                              compact_epilogue,
                              fused_intersect_compact_pairs,
                              fused_intersect_pairs,
                              fused_intersect_partial_pairs, round_up_lanes)
from .ops import (block_w_candidates, fused_intersect,
                  fused_intersect_compact, fused_intersect_partial,
                  kernel_path, resolve_block_w, seeded_candidates)
from .ref import (fused_intersect_compact_ref, fused_intersect_partial_ref,
                  fused_intersect_ref)

__all__ = [
    "MODE_TIDSET", "MODE_TID_TO_DIFF", "MODE_DIFFSET",
    "DEFAULT_BLOCK_W", "LANE", "MAX_PAIRS_PER_CALL", "round_up_lanes",
    "block_w_candidates", "seeded_candidates", "resolve_block_w",
    "kernel_path", "compact_epilogue",
    "fused_intersect", "fused_intersect_pairs", "fused_intersect_ref",
    "fused_intersect_compact", "fused_intersect_compact_pairs",
    "fused_intersect_compact_ref",
    "fused_intersect_partial", "fused_intersect_partial_pairs",
    "fused_intersect_partial_ref",
]
