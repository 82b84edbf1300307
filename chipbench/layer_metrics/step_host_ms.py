"""Serving engine: the host's part of a decode step.  Over the engine's
``serve.step`` phases that hold a ``decode`` span, the mean of the step's
time less its ``serve.readback`` (the wait for the step's tokens): table
building, transfers, dispatch, token bookkeeping, admission and chunk
dispatch.  None where the program writes no phases."""
import bisect

from layer_metrics._common import spans


def _inside(starts, ss, lo, hi):
    i = bisect.bisect_left(starts, lo)
    out = []
    while i < len(ss) and ss[i]["t_start"] <= hi:
        if ss[i]["t_end"] <= hi:
            out.append(ss[i])
        i += 1
    return out


def read(record):
    steps = spans(record, ["serve.step"])
    decodes = sorted(spans(record, ["decode"]), key=lambda s: s["t_start"])
    backs = sorted(spans(record, ["serve.readback"]),
                   key=lambda s: s["t_start"])
    d0 = [s["t_start"] for s in decodes]
    b0 = [s["t_start"] for s in backs]
    host = []
    for st in steps:
        lo, hi = st["t_start"], st["t_end"]
        if not _inside(d0, decodes, lo, hi):
            continue
        wait = sum(b["t_end"] - b["t_start"]
                   for b in _inside(b0, backs, lo, hi))
        host.append(hi - lo - wait)
    if not host:
        return None
    return 1e3 * sum(host) / len(host)
