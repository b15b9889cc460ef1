"""Itemset store (the answer) in a slide: seconds per slide of
``phase_s["support_map"]``, the program's ``slide.support_map`` span on
``WindowResult.support_map()``."""
from ._common import mean_of, phase


def read(run):
    return mean_of(run.records, phase("support_map"))
