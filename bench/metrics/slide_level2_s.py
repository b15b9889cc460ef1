"""Re-mine from cached counts: seconds per slide of ``phase_s["level2"]``,
the program's ``slide.level2`` span (frequent pairs read from the count
matrix, then their level-2 expand)."""
from ._common import mean_of, phase


def read(run):
    return mean_of(run.records, phase("level2"))
