"""Kernels: the paged chunked-prefill attention kernel (the block-table
Pallas kernel inside the engine's chunk program) against its roofline.
Work: each chunk's queries against the keys before and inside it
(``work.chunk_call``); time: the kernel's summed device time."""
import devtrace
from layer_metrics._common import roofline_share, total_work


def read(record):
    w = total_work(record)
    t = devtrace.kernel_seconds(record["trace"], "_chunk_batch_impl",
                                    "pallas_paged")
    return roofline_share(record, w.get("chunk_flops", 0),
                          w.get("chunk_bytes", 0), t)
