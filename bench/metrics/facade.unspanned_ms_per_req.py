"""Mean time per traced call, in ms, in the program's root ``sort`` span
covered neither by one of its child spans nor by device-busy time: what the
spans leave unexplained.  Layer: facade and engines."""
from bench import program_spans as ps


def read(run):
    p = ps.program(run)
    return None if p is None else ps.unspanned_ms_per_call(p)
