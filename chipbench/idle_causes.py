"""Split the chip's idle time in a traced window by its cause.

Each idle interval of the device (``tracer_clock.idle_intervals``, on
the span tracer's clock) is cut where requests were outstanding: outside
that it is ``nothing outstanding``; inside, each piece goes to the phase
the host was in (``repro.obs`` ``Tracer.phase``: ``serve.*``,
``gateway.*``, ``gc``; a collection outranks every other phase, which it
stops, and otherwise the innermost phase counts), or to ``unnamed``.
The causes add up to the window less the device's busy time.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from layer_metrics._intervals import intersect, length, pending
from tracer_clock import is_phase

# idle stretches at least this long, while a request was outstanding,
# are listed one by one
LONG_S = 0.1


def timeline(spans: List[Dict]) -> List[Tuple[float, float, str]]:
    """Sorted, disjoint stretches of host time, each named by the phase
    that holds it (``gc`` first, else the innermost, the latest begun)."""
    ph = [s for s in spans if is_phase(s["name"]) and s.get("t_end")
          is not None and s["t_end"] > s["t_start"]]
    pts = sorted([(s["t_start"], 1, i) for i, s in enumerate(ph)]
                 + [(s["t_end"], 0, i) for i, s in enumerate(ph)])
    active = set()
    out: List[Tuple[float, float, str]] = []
    prev = None
    for t, begins, i in pts:
        if prev is not None and t > prev and active:
            if any(ph[j]["name"] == "gc" for j in active):
                name = "gc"
            else:
                name = ph[max(active, key=lambda j: ph[j]["t_start"])]["name"]
            if out and out[-1][2] == name and out[-1][1] == prev:
                out[-1] = (out[-1][0], t, name)
            else:
                out.append((prev, t, name))
        if begins:
            active.add(i)
        else:
            active.discard(i)
        prev = t
    return out


def named(pieces, tl) -> List[Tuple[float, str]]:
    """(length, phase) of each part of ``pieces`` (sorted, disjoint),
    ``unnamed`` where no phase holds it."""
    out: List[Tuple[float, str]] = []
    j = 0
    for s, e in pieces:
        while j < len(tl) and tl[j][1] <= s:
            j += 1
        k, cur = j, s
        while cur < e:
            if k < len(tl) and tl[k][0] < e:
                a, b, name = tl[k]
                if a > cur:
                    out.append((min(a, e) - cur, "unnamed"))
                    cur = min(a, e)
                    continue
                out.append((min(b, e) - cur, name))
                cur = min(b, e)
                k += 1
            else:
                out.append((e - cur, "unnamed"))
                cur = e
    return out


def split(record: Dict) -> Optional[Dict]:
    """``{"seconds": {cause: s}, "long": [[s, s outstanding, cause], ...]}``
    (each long gap named by the cause of most of its outstanding time)
    for a traced
    run's record whose ``trace`` holds ``clock_offset_s`` and
    ``idle_intervals``; None where the trace holds no clock anchor or a
    request never came back."""
    t = record["trace"]
    when = pending(record)
    if t.get("clock_offset_s") is None or when is None:
        return None
    idle = t["idle_intervals"]
    tl = timeline(record["spans"])
    seconds: Dict[str, float] = {}
    for n, name in named(intersect(idle, when), tl):
        seconds[name] = seconds.get(name, 0.0) + n
    seconds["nothing outstanding"] = length(idle) - sum(seconds.values())
    long = []
    for iv in idle:
        if iv[1] - iv[0] < LONG_S:
            continue
        by: Dict[str, float] = {}
        for n, name in named(intersect([iv], when), tl):
            by[name] = by.get(name, 0.0) + n
        if sum(by.values()) >= LONG_S:
            long.append([iv[1] - iv[0], sum(by.values()),
                         max(by, key=by.get)])
    return {"seconds": seconds, "long": sorted(long, reverse=True)}
