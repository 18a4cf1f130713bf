"""Bytes-bound roofline share of the XLA radix sort: the least time the
calls' problem bytes (keys in, one int32 index out per key) take at the
chip's published HBM bandwidth, over the device time of the runs of
``jit_radix_sort_keys``, in %.  HBM bandwidth is the only published bound
for integer sorting on the chip (bench/roofline.py).  Layer: XLA
machines."""
from bench import roofline
from bench import trace as tr

MODULE = "jit_radix_sort_keys"


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    ns = tr.module_ns(run.trace, MODULE)
    if not ns:
        return None
    return roofline.share_pct(roofline.answered_bytes(run),
                              run.peaks["hbm_bytes_per_s"], ns / 1e9)
