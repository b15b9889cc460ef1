"""Per-shard vertical build: seconds per mine of ``phase_s["shard_build"]``,
the program's ``mine.shard_build`` span inside ``mine.vertical`` -- from the
upload of each shard's CSR slice to the placed, word-sharded frontier."""
from ._common import mean_of, phase


def read(run):
    return mean_of(run.records, phase("shard_build"))
