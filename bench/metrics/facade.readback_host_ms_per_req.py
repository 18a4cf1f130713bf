"""Mean time per traced call, in ms, in the program's ``sort.readback`` spans
during which the device ran nothing: the copy to the host and its
synchronisation, not the wait for the kernel (device time, read by the
device metrics).  Layer: facade and engines."""
from bench import program_spans as ps


def read(run):
    p = ps.program(run)
    return None if p is None else ps.host_ms_per_call(p, "sort.readback")
