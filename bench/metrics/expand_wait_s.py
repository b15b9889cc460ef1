"""Engine: seconds per mine the host is blocked on the expansions' reads,
waiting on the kernel (``phase_s["expand_wait"]``, the program's
``mine.expand_wait`` spans inside level 2 and the bottom-up levels)."""
from ._common import mean_of, phase


def read(run):
    return mean_of(run.records, phase("expand_wait"))
