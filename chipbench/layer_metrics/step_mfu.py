"""Whole step: the model operations of every finished request (prompt
tokens prefilled and output tokens decoded: matmuls, the head where it
is computed, attention over the live context; ``work.request_work``) over
the chip's bf16 peak times the traced window."""
from layer_metrics._common import total_work


def read(record):
    window = record["trace"]["window_s"]
    flops = total_work(record).get("model_flops", 0)
    if not flops or window <= 0 or record["trace"]["busy_s"] <= 0:
        return None
    return 100.0 * flops / (record["peaks"]["bf16_flops_per_s"] * window)
