"""Mean per traced call of the program's ``readbacks`` counter: device arrays
read back to the host.  Layer: facade and engines."""
from bench import program_spans as ps


def read(run):
    p = ps.program(run)
    return None if p is None else ps.count_per_call(p, "readbacks")
