"""Device time of the fused TNS Pallas kernel per traced call: the
``tpu_custom_call`` operations inside runs of ``jit__fused_tns_rank``,
summed, over the number of calls.  Layer: kernels (kernels/fused_tns.py).
Reads nothing where the kernel did not run."""
from bench import trace as tr

MODULE, MARKER = "jit__fused_tns_rank", "tpu_custom_call"


def read(run):
    if run.trace is None:
        return None
    ns = tr.kernel_ns(run.trace, MODULE, MARKER)
    calls = tr.count_spans(run.trace, "sort_call")
    return ns / calls / 1e6 if ns and calls else None
