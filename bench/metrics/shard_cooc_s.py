"""Sharded level-2 co-occurrence pass: seconds per mine of
``phase_s["shard_cooc"]``, the program's ``mine.shard_cooc`` span inside
``mine.tri_matrix`` -- shard-local counts, one psum, one read."""
from ._common import mean_of, phase


def read(run):
    return mean_of(run.records, phase("shard_cooc"))
