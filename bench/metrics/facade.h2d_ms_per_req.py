"""Mean self time per traced call, in ms, of the program's ``sort.h2d`` spans:
host arrays handed to the device (``jnp.asarray``).  Layer: facade and
engines."""
from bench import program_spans as ps


def read(run):
    p = ps.program(run)
    return None if p is None else ps.span_ms_per_call(p, "sort.h2d")
