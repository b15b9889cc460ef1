"""Shared arithmetic of the per-layer metric readers."""
from __future__ import annotations

from typing import Optional


def mean_of(records: list, get) -> Optional[float]:
    """Mean of ``get(record)`` over the records where it is not None."""
    vals = [v for v in (get(r) for r in records) if v is not None]
    return sum(vals) / len(vals) if vals else None


def phase(name: str):
    return lambda r: (r.get("phase_s") or {}).get(name)


def idle_share(run) -> Optional[float]:
    if run.trace is None or run.trace["window_s"] <= 0:
        return None
    return 1.0 - run.trace["busy_s"] / run.trace["window_s"]
