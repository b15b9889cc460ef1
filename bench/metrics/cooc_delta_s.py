"""Incremental co-occurrence counts: seconds per slide of
``phase_s["cooc_delta"]``, the program's ``slide.cooc_delta`` span inside
``slide.push`` (the admitted and the evicted block's count passes)."""
from ._common import mean_of, phase


def read(run):
    return mean_of(run.records, phase("cooc_delta"))
