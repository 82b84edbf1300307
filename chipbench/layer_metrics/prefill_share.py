"""Serving engine: time in ``prefill`` and ``prefill_chunk`` spans over
the time in those and ``decode`` spans."""
from layer_metrics._common import spans


def _time(ss):
    return sum(s["t_end"] - s["t_start"] for s in ss)


def read(record):
    pre = _time(spans(record, ["prefill", "prefill_chunk"]))
    total = pre + _time(spans(record, ["decode"]))
    return 100.0 * pre / total if total > 0 else None
