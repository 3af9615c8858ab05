"""The program's own work spans in a reduced trace.

``repro.obs.trace`` puts each leaf step of the program's host work on the
profiler's clock as a ``TraceAnnotation`` whose name starts with
``repro.`` (``repro.oracle.tokenize``, ``repro.engine.launch``, ...; the
list is in docs/observability.md).  The work spans of one thread never
nest or overlap, so their summed durations are host seconds spent in
those steps.  A program without these spans leaves the list empty, and a
metric that reads it then reads nothing.
"""
from __future__ import annotations

from chipbench import xplane

PREFIX = "repro."


def work_spans(trace) -> list:
    """[(start_ns, end_ns, name)] of the program's work spans."""
    return [ev for ev in trace.host if ev[2].startswith(PREFIX)]


def _matches(name: str, patterns) -> bool:
    # a pattern ending in "." names a family ("repro.oracle."), any other
    # pattern one step
    return any(name.startswith(p) if p.endswith(".") else name == p
               for p in patterns)


def seconds(spans, *patterns) -> float:
    """Summed seconds of the spans named by ``patterns``."""
    return sum(e - s for s, e, n in spans if _matches(n, patterns)) / 1e9


def count(spans, *patterns) -> int:
    return sum(1 for _, _, n in spans if _matches(n, patterns))


def overlap_s(a, b) -> float:
    """Seconds covered by both of two lists of [start, end) intervals."""
    a, b = xplane.merged(a), xplane.merged(b)
    i = j = 0
    tot = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            tot += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot / 1e9


def idle_gaps(trace) -> list:
    """[(start_ns, end_ns)] between the first chip's busy intervals."""
    busy = xplane.merged((s, e) for s, e, _ in next(iter(trace.ops.values())))
    return [(a[1], b[0]) for a, b in zip(busy, busy[1:])]
