"""Host driver between levels in a slide: seconds per slide of
``phase_s["bottom_up"]``, the program's ``slide.bottom_up`` span (levels
>= 3)."""
from ._common import mean_of, phase


def read(run):
    return mean_of(run.records, phase("bottom_up"))
