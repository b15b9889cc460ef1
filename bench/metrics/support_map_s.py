"""Itemset store (the answer): seconds per mine of ``support_map()``, the
full (itemset, support) map built from the mined levels
(``phase_s["support_map"]``, the program's ``mine.support_map`` span)."""
from ._common import mean_of, phase


def read(run):
    return mean_of(run.records, phase("support_map"))
