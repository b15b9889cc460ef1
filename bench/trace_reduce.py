"""From a profiler trace to device busy time, kernel time and idle gaps.

The reduction every traced run uses, kept with the benchmark so that the
numbers are computed the same way for every commit:

* ``busy_s``: per device, the union of the intervals in which an XLA
  operation ran, inside the traced window, averaged over the devices used;
* ``module_s``: device seconds per compiled program (the ``XLA Modules``
  line), keyed by the jitted function's name with the ``jit_`` prefix and
  the fingerprint stripped -- the name a kernel reader looks up;
* ``device_ops``: the programs that took the most device time;
* ``idle_gaps``: device idle time inside the window, split by what the host
  was doing, as told by the host spans the harness recorded.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

__all__ = ["Trace", "load", "union_length", "program_name", "reduce"]

Interval = Tuple[int, int, str]          # (start_ns, end_ns, name)

_FINGERPRINT = re.compile(r"\(\d+\)$")


@dataclasses.dataclass
class Trace:
    ops: Dict[str, List[Interval]]       # device -> XLA ops
    modules: Dict[str, List[Interval]]   # device -> XLA modules (programs)
    host: List[Interval]                 # host spans (TraceAnnotation)


def program_name(event_name: str) -> str:
    """``jit_fused_intersect_compact_pairs(1234)`` ->
    ``fused_intersect_compact_pairs``."""
    name = _FINGERPRINT.sub("", event_name.strip())
    return name[4:] if name.startswith("jit_") else name


def load(log_dir: str, host_prefix: str = "bench.") -> Trace:
    """Read the one ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}, found "
                           f"{len(paths)}")
    data = ProfileData.from_file(paths[0])
    ops: Dict[str, List[Interval]] = defaultdict(list)
    modules: Dict[str, List[Interval]] = defaultdict(list)
    host: List[Interval] = []
    for plane in data.planes:
        device = plane.name.startswith("/device:") and "CUSTOM" not in plane.name
        for line in plane.lines:
            if device and line.name in ("XLA Ops", "XLA Modules"):
                dst = ops if line.name == "XLA Ops" else modules
                dst[plane.name].extend(
                    (int(e.start_ns), int(e.start_ns + e.duration_ns), e.name)
                    for e in line.events)
            elif not device:
                host.extend(
                    (int(e.start_ns), int(e.start_ns + e.duration_ns), e.name)
                    for e in line.events if e.name.startswith(host_prefix))
    return Trace(dict(ops), dict(modules), sorted(host))


def _merge(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def union_length(intervals: Sequence[Tuple[int, int]], lo: int, hi: int) -> int:
    """Nanoseconds of ``[lo, hi)`` covered by at least one interval."""
    clipped = [(max(s, lo), min(e, hi)) for s, e in intervals]
    return sum(e - s for s, e in _merge([(s, e) for s, e in clipped if e > s]))


def _idle_by_label(busy: List[Tuple[int, int]], lo: int, hi: int,
                   labels: Sequence[Interval]) -> Dict[str, int]:
    """Idle nanoseconds of ``[lo, hi)`` (outside the merged ``busy``
    intervals) split by the host label covering them; ``labels`` must not
    overlap.  Idle time under no label counts as ``between``."""
    idle, t = [], lo
    for s, e in busy:
        if s > t:
            idle.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        idle.append((t, hi))
    out: Dict[str, int] = defaultdict(int)
    spans = sorted(labels)
    li = 0
    for a, b in idle:
        if b <= a:
            continue
        covered = 0
        while li < len(spans) and spans[li][1] <= a:
            li += 1
        j = li
        while j < len(spans) and spans[j][0] < b:
            s, e, name = spans[j]
            part = min(e, b) - max(s, a)
            if part > 0:
                out[name] += part
                covered += part
            j += 1
        if b - a - covered > 0:
            out["between"] += b - a - covered
    return dict(out)


def reduce(trace: Trace, window: Tuple[int, int],
           labels: Optional[Sequence[Interval]] = None, top: int = 10) -> dict:
    """Busy and window seconds, device seconds per program, and the
    ``breakdown`` lists of a traced run's result."""
    lo, hi = window
    if hi <= lo:
        raise ValueError(f"empty traced window {window}")
    devices = sorted(trace.ops)
    if not devices:
        raise RuntimeError("the trace holds no device operations")
    busy_ns = [union_length([(s, e) for s, e, _ in trace.ops[d]], lo, hi)
               for d in devices]
    module_ns: Dict[str, int] = defaultdict(int)
    for d in trace.modules:
        for s, e, name in trace.modules[d]:
            if lo <= s and e <= hi:
                module_ns[program_name(name)] += e - s
    first = _merge([(s, e) for s, e, _ in trace.ops[devices[0]]])
    idle = _idle_by_label(first, lo, hi, labels or [])
    n_dev = len(devices)
    return {
        "busy_s": sum(busy_ns) / n_dev / 1e9,
        "window_s": (hi - lo) / 1e9,
        "module_s": {k: v / n_dev / 1e9 for k, v in module_ns.items()},
        "device_ops": [[k, v / n_dev / 1e9] for k, v in
                       sorted(module_ns.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[k, v / 1e9] for k, v in
                      sorted(idle.items(), key=lambda kv: -kv[1])[:top]],
    }
