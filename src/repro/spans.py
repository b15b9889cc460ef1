"""Named host spans on the profiler's clock.

``span`` times one phase of a mine or of a window slide.  It adds the
elapsed seconds to ``into[name]`` (repeated spans accumulate) and opens a
``jax.profiler.TraceAnnotation`` named ``prefix + name`` over the same
interval, so a profiler trace (``jax.profiler.trace``) shows every phase on
the clock of the device ops it waited for.  The batch miner's spans are
prefixed ``mine.``, the streaming miner's ``slide.``.

There is no switch: the spans are recorded exactly when a profiler session
is active, and outside one an annotation costs about a microsecond.
"""
from __future__ import annotations

import time
from typing import Dict, Optional

from jax.profiler import TraceAnnotation

__all__ = ["span"]


class span:
    """``with span("vertical", phase_s, prefix="mine."):`` times the block
    into ``phase_s["vertical"]`` and annotates it as ``mine.vertical``;
    keyword ``args`` become the trace event's arguments."""

    __slots__ = ("name", "into", "_note", "_t0")

    def __init__(self, name: str, into: Optional[Dict[str, float]] = None,
                 *, prefix: str, **args):
        self.name = name
        self.into = into
        self._note = TraceAnnotation(prefix + name, **args)

    def __enter__(self) -> "span":
        self._note.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        if self.into is not None:
            self.into[self.name] = (self.into.get(self.name, 0.0)
                                    + time.perf_counter() - self._t0)
        self._note.__exit__(*exc)
