"""Interval arithmetic for the readers that set host spans against the
requests of a traced run's record."""
from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Sequence, Tuple


def union(intervals: Iterable[Sequence[float]]) -> List[Tuple[float, float]]:
    """Sorted, disjoint cover of ``intervals``."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted((a, b) for a, b in intervals if b > a):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def intersect(a: Sequence[Sequence[float]], b: Sequence[Sequence[float]]
              ) -> List[Tuple[float, float]]:
    """Intersection of two sorted, disjoint interval lists."""
    out: List[Tuple[float, float]] = []
    i = j = 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def length(intervals: Iterable[Sequence[float]]) -> float:
    return sum(e - s for s, e in intervals)


def pending(record: Dict) -> Optional[List[Tuple[float, float]]]:
    """When some request of the window was outstanding: the union of each
    request's due time to its result, on the tracer's clock; None where
    a request never came back."""
    reqs = record["requests"]
    if not reqs or any(r["r_end"] is None for r in reqs):
        return None
    return union((r["due"], r["r_end"]) for r in reqs)
