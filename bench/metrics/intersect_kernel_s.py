"""Kernel: device seconds per mine of the jitted fused intersection
(``fused_intersect_compact_pairs``), from the trace's ``XLA Modules``."""

PROGRAM = "fused_intersect_compact_pairs"


def read(run):
    if run.trace is None or not run.records:
        return None
    s = run.trace["module_s"].get(PROGRAM)
    return s / len(run.records) if s else None
