"""Host driver between levels: seconds of ``phase_s["bottom_up"]`` per mine
(levels >= 3: class segmentation, pair generation, the expansions)."""
from ._common import mean_of, phase


def read(run):
    return mean_of(run.records, phase("bottom_up"))
