"""Shared by the per-layer readers: the record of a traced run."""
from __future__ import annotations

from typing import Dict, Iterable, List

import work


def finished(record: Dict) -> List[Dict]:
    return [r for r in record["requests"] if r["ok"]]


def spans(record: Dict, names: Iterable[str]) -> List[Dict]:
    names = set(names)
    return [s for s in record["spans"]
            if s["name"] in names and s.get("t_end") is not None]


def total_work(record: Dict) -> Dict[str, int]:
    """The algorithm's work over every finished request of the window."""
    m = record["spec"].model
    chunk = record["spec"].engine["prefill_chunk"]
    out: Dict[str, int] = {}
    for r in finished(record):
        w = work.request_work(m, len(r["prompt"]), len(r["output"]), chunk)
        for k, v in w.items():
            out[k] = out.get(k, 0) + v
    return out


def roofline_share(record: Dict, flops: int, nbytes: int, seconds: float):
    """Least time the chip could take for the work, over the time taken,
    in percent; None where there was no work or no kernel time."""
    if not flops or seconds <= 0:
        return None
    p = record["peaks"]
    least = max(flops / p["bf16_flops_per_s"], nbytes / p["hbm_bytes_per_s"])
    return 100.0 * least / seconds
