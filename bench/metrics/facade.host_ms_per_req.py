"""Mean over the traced calls of the call's host span less the device-busy
time inside it: what ``sort()``, the engine and the kernel wrappers spend
on the host per call.  Layer: facade and engines."""
from bench import trace as tr


def read(run):
    if run.trace is None:
        return None
    per = tr.host_minus_device_ns(run.trace, "sort_call")
    return sum(per) / len(per) / 1e6 if per else None
