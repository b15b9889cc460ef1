"""Level-2 co-occurrence pass: seconds per mine of the triangular count
matrix alone, without the level-2 expand (``phase_s["cooc"]``, the
program's ``mine.cooc`` span inside ``mine.tri_matrix``)."""
from ._common import mean_of, phase


def read(run):
    return mean_of(run.records, phase("cooc"))
