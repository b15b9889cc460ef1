"""Kernel: share of the HBM roofline, in percent.

The least time the chip could take -- the bytes Eclat over dense rows
needs for the window's mines (``needed_bytes``, from the reference answer
and the database's shape) at the published HBM bandwidth -- over the
device time of the fused intersection.  The VPU's integer peak is not
published, so the bound is HBM bandwidth alone."""
from .intersect_kernel_s import PROGRAM


def read(run):
    if run.trace is None or not run.records:
        return None
    kernel_s = run.trace["module_s"].get(PROGRAM)
    if not kernel_s:
        return None
    need = sum(run.cell.needed_bytes(r["input"]) for r in run.records)
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] / kernel_s
