"""Kernel: the word-sharded executable's share of one chip's HBM roofline,
in percent.

Each of the cell's shards reads and writes its own slice of every row, so a
chip's least time is its share of the bytes Eclat over dense rows needs for
the window's mines (``needed_bytes`` over the number of shards) at the
published HBM bandwidth; the share is that over the device time of
``fused_intersect_partial_compact`` (averaged over the devices)."""
from .partial_kernel_s import PROGRAM


def read(run):
    if run.trace is None or not run.records:
        return None
    kernel_s = run.trace["module_s"].get(PROGRAM)
    if not kernel_s:
        return None
    need = sum(run.cell.needed_bytes(r["input"]) for r in run.records)
    return 100.0 * need / run.cell.n_shards / run.peaks["hbm_bytes_per_s"] / kernel_s
