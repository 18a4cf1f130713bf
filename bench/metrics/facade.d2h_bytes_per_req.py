"""Mean per traced call of the program's ``d2h_bytes`` counter: bytes read
back to the host (``sort.readback``).  Layer: facade and engines."""
from bench import program_spans as ps


def read(run):
    p = ps.program(run)
    return None if p is None else ps.count_per_call(p, "d2h_bytes")
