"""Level-2 co-occurrence: seconds of ``phase_s["tri_matrix"]`` per mine
(the triangular matrix and the level-2 expand)."""
from ._common import mean_of, phase


def read(run):
    return mean_of(run.records, phase("tri_matrix"))
