"""Mean self time per traced call, in ms, of the program's
``sort.rank_to_perm`` spans: the rank ring inverted on the host (``pallas-
tns``); 0 on engines that do none.  Layer: facade and engines."""
from bench import program_spans as ps


def read(run):
    p = ps.program(run)
    return None if p is None else ps.span_ms_per_call(p, "sort.rank_to_perm")
