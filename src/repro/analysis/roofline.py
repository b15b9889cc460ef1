"""Roofline derivation from the compiled dry-run artifact.

Hardware model (TPU v5e, per assignment):
    peak_flops = 197e12   bf16 FLOP/s per chip
    hbm_bw     = 819e9    B/s per chip
    link_bw    = 50e9     B/s per ICI link

Three terms per (arch x shape x mesh) cell, all in seconds-per-step on the
per-device program:

    compute    = device_FLOPs / peak_flops
    memory     = device_bytes / hbm_bw
    collective = device_wire_bytes / link_bw

Scan calibration: XLA's HloCostAnalysis (and a textual collective count)
visits a while-loop body ONCE, so a scanned 48-layer stage reports ~1 layer
of cost.  We therefore compile, per layer-kind k, two tiny depth variants
(full width, ShapeDtypeStruct only) whose patterns differ by exactly one
layer of kind k; the cost delta is that layer's true per-iteration cost and

    total = base + sum_k (count_k - base_count_k) * delta_k

reconstructs the full-depth cost exactly (stage bodies are homogeneous).
The full-size compile is still performed unconditionally — it is the
dry-run deliverable (memory_analysis / sharding proof); only FLOP/byte
totals use the calibrated reconstruction.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

PEAK_FLOPS = 197e12
HBM_BW = 819e9
LINK_BW = 50e9

__all__ = ["PEAK_FLOPS", "HBM_BW", "LINK_BW", "RooflineTerms", "CellReport",
           "roofline_terms", "model_flops", "measure_compiled",
           "calibration_patterns", "intersect_cost", "VPU_LANES",
           "VPU_WORD_OPS"]

# Vector-unit model for the mining hot loop (the fused gather+AND+popcount
# kernel operates on uint32 words on the VPU, not the MXU): 8x128 lanes per
# cycle at ~940 MHz -> word-ops/s.  popcount + AND + the accumulator add is
# ~3 VPU ops per word.
VPU_LANES = 8 * 128
VPU_CLOCK = 0.94e9
VPU_WORD_OPS = VPU_LANES * VPU_CLOCK


@dataclasses.dataclass
class RooflineTerms:
    compute_s: float
    memory_s: float
    collective_s: float

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def compute_fraction(self) -> float:
        """Fraction of the step the MXUs could be busy if everything else
        overlapped perfectly — the roofline score for compute-bound cells."""
        return self.compute_s / max(self.bound_s, 1e-30)

    def to_dict(self):
        return {"compute_s": self.compute_s, "memory_s": self.memory_s,
                "collective_s": self.collective_s, "dominant": self.dominant,
                "compute_fraction": self.compute_fraction}


@dataclasses.dataclass
class CellReport:
    arch: str
    shape: str
    mesh: str
    kind: str
    n_devices: int
    flops_per_device: float
    bytes_per_device: float
    wire_bytes_per_device: float
    collective_counts: Dict[str, int]
    collective_bytes: Dict[str, float]
    memory: Dict[str, float]
    terms: RooflineTerms
    model_flops_total: float
    hlo_model_ratio: float
    compile_s: float
    calibrated: bool
    notes: str = ""

    def to_dict(self):
        d = dataclasses.asdict(self)
        d["terms"] = self.terms.to_dict()
        return d


def measure_compiled(compiled, n_devices: int):
    """Raw (uncalibrated) per-device cost/memory/collective measurements."""
    from .hlo_parse import parse_collectives
    ca = compiled.cost_analysis() or {}
    flops = float(ca.get("flops", 0.0))
    nbytes = float(ca.get("bytes accessed", 0.0))
    text = compiled.as_text()
    coll = parse_collectives(text, n_devices)
    mem = compiled.memory_analysis()
    memory = {
        "argument_gb": mem.argument_size_in_bytes / 1e9,
        "output_gb": mem.output_size_in_bytes / 1e9,
        "temp_gb": mem.temp_size_in_bytes / 1e9,
        "alias_gb": mem.alias_size_in_bytes / 1e9,
        "peak_gb": (mem.argument_size_in_bytes + mem.output_size_in_bytes
                    + mem.temp_size_in_bytes - mem.alias_size_in_bytes) / 1e9,
    }
    return flops, nbytes, coll, memory


def roofline_terms(flops, nbytes, wire_bytes) -> RooflineTerms:
    return RooflineTerms(
        compute_s=flops / PEAK_FLOPS,
        memory_s=nbytes / HBM_BW,
        collective_s=wire_bytes / LINK_BW,
    )


def intersect_cost(q: int, w: int, block_w: int, *,
                   ops_per_word: float = 3.0) -> RooflineTerms:
    """Roofline terms for one fused gather+AND+popcount level expansion.

    The kernel reads both parent rows once per word block and writes the
    intersection once, so per pair the HBM traffic is ``3 * w * 4`` bytes
    plus a per-block-step fixed overhead (the DMA descriptor + accumulator
    spill each of the ``ceil(w / block_w)`` grid steps pays — the term that
    penalizes tiny ``block_w``); compute is ``ops_per_word`` VPU word-ops
    per word (AND + popcount + accumulate).  A ``block_w`` wider than the
    lane-padded row is modeled as reading the padded row (the term that
    penalizes over-wide blocks on narrow frontiers).  Used by
    ``kernels.fused_intersect.ops.resolve_block_w`` to pick the tile width:
    the best-predicted candidate is the one compiled.
    """
    q = max(int(q), 1)
    w = max(int(w), 1)
    bw = max(int(block_w), 1)
    n_steps = -(-w // bw)                 # ceil: grid steps along the word axis
    w_padded = n_steps * bw               # zero-padded words actually streamed
    # 2 row reads + 1 intersection write, 4 bytes/word, plus ~512B of
    # per-step DMA/bookkeeping overhead per operand (3 operands)
    step_overhead_bytes = 3 * 512.0
    bytes_moved = q * (3.0 * w_padded * 4.0 + n_steps * step_overhead_bytes)
    word_ops = q * w_padded * ops_per_word
    return RooflineTerms(
        compute_s=word_ops / VPU_WORD_OPS,
        memory_s=bytes_moved / HBM_BW,
        collective_s=0.0,
    )


def model_flops(cfg, shape) -> float:
    """Analytic MODEL_FLOPS: 6*N_active*D for train, 2*N_active*D for
    inference (D = tokens processed this step), attention excluded — the
    reported HLO/MODEL ratio absorbs attention + remat overheads."""
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        return 6.0 * n_active * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n_active * shape.global_batch * shape.seq_len
    return 2.0 * n_active * shape.global_batch  # decode: one token per seq


def calibration_patterns(cfg) -> Tuple[Tuple[str, ...], Dict[str, Tuple[str, ...]], Dict[str, int]]:
    """Base pattern (one layer per kind) + per-kind +1 variants + true counts."""
    pattern = cfg.layer_pattern()
    kinds: List[str] = []
    counts: Dict[str, int] = {}
    for k in pattern:
        counts[k] = counts.get(k, 0) + 1
        if k not in kinds:
            kinds.append(k)
    base = tuple(kinds)
    variants = {k: tuple(list(base) + [k]) for k in kinds}
    return base, variants, counts
