"""Mean per traced call of the program's ``h2d_bytes`` counter: bytes handed
to the device (``sort.h2d``).  Layer: facade and engines."""
from bench import program_spans as ps


def read(run):
    p = ps.program(run)
    return None if p is None else ps.count_per_call(p, "h2d_bytes")
