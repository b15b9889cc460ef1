"""Host ingest: seconds of ``phase_s["vertical"]`` per mine (the packed
vertical database, ``core/vertical.py`` and ``core/bitmap.py``)."""
from ._common import mean_of, phase


def read(run):
    return mean_of(run.records, phase("vertical"))
