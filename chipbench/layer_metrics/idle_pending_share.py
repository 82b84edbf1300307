"""Device: the share of the traced window in which the chip sat idle
while some request was outstanding (due, not yet answered).

The chip works only for outstanding requests, and every request of the
window is due and answered inside it, so the idle time within the
requests' due-to-result union is that union's length less the device's
busy time (``devtrace``), and needs no common clock.  None where a
request never came back."""
from layer_metrics._intervals import length, pending


def read(record):
    t = record["trace"]
    when = pending(record)
    if when is None or t["window_s"] <= 0 or t["busy_s"] <= 0:
        return None
    return 100.0 * (length(when) - t["busy_s"]) / t["window_s"]
