"""Device: the share of the traced window in which no operation ran on
the chip (``trace.py``: one minus the union of operation intervals)."""


def read(record):
    t = record["trace"]
    if t["window_s"] <= 0 or t["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
