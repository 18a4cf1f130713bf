"""Share of the traced window in which no operation ran on the device:
1 - (union of the ``XLA Ops`` intervals) / (window), averaged over the
devices.  Layer: the device."""
from bench import trace as tr


def read(run):
    if run.trace is None:
        return None
    return 100.0 * (1.0 - tr.busy_ns(run.trace) / tr.window_ns(run.trace))
