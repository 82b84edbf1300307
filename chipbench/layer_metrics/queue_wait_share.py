"""Gateway dispatch: the share of request latency spent before the
engine starts the request's micro-batch (due time to ``e_start`` of the
invocation's timestamps), summed over finished requests."""
from layer_metrics._common import finished


def read(record):
    done = finished(record)
    total = sum(r["r_end"] - r["due"] for r in done)
    if not done or total <= 0:
        return None
    return 100.0 * sum(r["e_start"] - r["due"] for r in done) / total
