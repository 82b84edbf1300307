"""Warm runtime and JIT: seconds of Python garbage collection (the
program's ``gc`` phases, one a collection, in any thread) while the
window's requests were served, from the first due time to the last
result.  None where the program writes no phases."""
from layer_metrics._common import spans
from layer_metrics._intervals import intersect, length, pending, union


def read(record):
    when = pending(record)
    if when is None or not spans(record, ["serve.step"]):
        return None
    gc = union((s["t_start"], s["t_end"]) for s in spans(record, ["gc"]))
    return length(intersect(gc, [(when[0][0], when[-1][1])]))
