"""Device time of the XLA radix sort per traced call: the runs of
``jit_radix_sort_keys``, summed, over the number of calls.  Layer: XLA
machines (core/radix_select.py).  Reads nothing where it did not run."""
from bench import trace as tr

MODULE = "jit_radix_sort_keys"


def read(run):
    if run.trace is None:
        return None
    ns = tr.module_ns(run.trace, MODULE)
    calls = tr.count_spans(run.trace, "sort_call")
    return ns / calls / 1e6 if ns and calls else None
