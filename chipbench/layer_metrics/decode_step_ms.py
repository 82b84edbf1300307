"""Model step: mean duration of the engine's ``decode`` spans; each ends
when the step's tokens are back on the host."""
from layer_metrics._common import spans


def read(record):
    steps = spans(record, ["decode"])
    if not steps:
        return None
    return 1e3 * sum(s["t_end"] - s["t_start"] for s in steps) / len(steps)
