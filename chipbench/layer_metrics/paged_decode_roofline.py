"""Kernels: the paged decode attention kernel (the block-table Pallas
kernel inside the engine's decode program) against its roofline.  Work:
each decode step's query against the live keys and values of its
sequence (``work.decode_call``); time: the kernel's summed device time."""
import devtrace
from layer_metrics._common import roofline_share, total_work


def read(record):
    w = total_work(record)
    t = devtrace.kernel_seconds(record["trace"], "_decode_paged_impl",
                                    "pallas_paged")
    return roofline_share(record, w.get("decode_flops", 0),
                          w.get("decode_bytes", 0), t)
