"""Put a JAX profiler trace on the program's span-tracer clock.

The program's phases (``repro.obs`` ``Tracer.phase`` and its ``gc``
counter) write each host annotation with its start on the tracer's clock
as the argument ``t``.  From a trace's planes (``ProfileData.planes``):

* :func:`clock_offset_s`: the median of ``start - t`` over the host
  events that carry ``t``, in seconds (None where none does);
* :func:`idle_intervals`: every stretch of the traced window (the
  ``chipbench.window`` annotation, else first to last operation) in
  which the first device ran no operation, in seconds, on the tracer's
  clock where an offset is given.
"""
from __future__ import annotations

import statistics
from typing import List, Optional

import devtrace


def is_phase(name: str) -> bool:
    """A phase annotation of the program, or its collection counter."""
    return name.startswith(("serve.", "gateway.")) or name == "gc"


def _host_events(planes):
    for p in planes:
        if p.name.startswith("/host:CPU"):
            for line in p.lines:
                yield from line.events


def clock_offset_s(planes) -> Optional[float]:
    offsets = []
    for e in _host_events(planes):
        if is_phase(e.name):
            t = dict(e.stats).get("t")
            if isinstance(t, (int, float)):
                offsets.append(e.start_ns * 1e-9 - t)
    return statistics.median(offsets) if offsets else None


def idle_intervals(planes, offset: Optional[float] = None
                   ) -> List[List[float]]:
    planes = list(planes)
    marks = [(e.start_ns, e.start_ns + e.duration_ns)
             for e in _host_events(planes) if e.name == devtrace.WINDOW]
    dev = next((p for p in planes if p.name.startswith("/device:TPU:")),
               None)
    ops = [] if dev is None else [
        e for ln in dev.lines if ln.name == "XLA Ops" for e in ln.events]
    busy: List[List[float]] = []
    for s, e in sorted((e.start_ns, e.start_ns + e.duration_ns)
                       for e in ops):
        if busy and s <= busy[-1][1]:
            busy[-1][1] = max(busy[-1][1], e)
        else:
            busy.append([s, e])
    if marks:
        lo, hi = marks[0][0], marks[-1][1]
    elif busy:
        lo, hi = busy[0][0], busy[-1][1]
    else:
        return []
    edges = [lo] + [min(max(x, lo), hi) for iv in busy for x in iv] + [hi]
    shift = offset or 0.0
    return [[edges[i] * 1e-9 - shift, edges[i + 1] * 1e-9 - shift]
            for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
