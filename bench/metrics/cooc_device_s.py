"""Incremental co-occurrence counts on the device: seconds per slide of the
jitted ``_cooc_block``, from the trace's ``XLA Modules``."""

PROGRAM = "_cooc_block"


def read(run):
    if run.trace is None or not run.records:
        return None
    s = run.trace["module_s"].get(PROGRAM)
    return s / len(run.records) if s else None
