"""Streaming ingest: seconds per slide of ``phase_s["push"]``, the program's
``slide.push`` span (the ring write and both co-occurrence deltas)."""
from ._common import mean_of, phase


def read(run):
    return mean_of(run.records, phase("push"))
