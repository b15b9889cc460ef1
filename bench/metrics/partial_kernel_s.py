"""Kernel: device seconds per mine of the word-sharded fused intersection's
executable (``fused_intersect_partial_compact``: the partial kernel on each
shard's words, the psum of the counts and the survivor compaction), from
the trace's ``XLA Modules``, averaged over the devices."""

PROGRAM = "fused_intersect_partial_compact"


def read(run):
    if run.trace is None or not run.records:
        return None
    s = run.trace["module_s"].get(PROGRAM)
    return s / len(run.records) if s else None
