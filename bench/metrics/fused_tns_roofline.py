"""Bytes-bound roofline share of the fused TNS kernel: the least time the
calls' problem bytes (keys in, m int32 indices out per array) take at the
chip's published HBM bandwidth, over the kernel's device time, in %.  No
integer vector peak is published for the chip, so HBM bandwidth is the
only bound (bench/roofline.py).  Layer: kernels."""
from bench import roofline
from bench import trace as tr

MODULE, MARKER = "jit__fused_tns_rank", "tpu_custom_call"


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    ns = tr.kernel_ns(run.trace, MODULE, MARKER)
    if not ns:
        return None
    return roofline.share_pct(roofline.answered_bytes(run),
                              run.peaks["hbm_bytes_per_s"], ns / 1e9)
