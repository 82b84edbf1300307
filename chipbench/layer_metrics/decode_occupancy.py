"""Serving engine: rows decoding per decode step (the ``decode`` spans'
``tokens``) over the engine's slots, averaged over the steps."""
from layer_metrics._common import spans


def read(record):
    steps = spans(record, ["decode"])
    if not steps:
        return None
    slots = record["spec"].engine["slots"]
    return 100.0 * sum(s["attrs"]["tokens"] for s in steps) / (
        slots * len(steps))
