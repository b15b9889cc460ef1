"""Blocking device-to-host reads per slide: ``counts["host_reads"]`` of a
slide's stats (the push's co-occurrence blocks plus the engine's reads)."""
from ._common import mean_of


def read(run):
    return mean_of(run.records, lambda r: r.get("host_reads"))
