"""Mean self time per traced call, in ms, of the program's ``sort.params``
spans: the autotune table lookup of ``pallas-tns``; 0 on engines that do
none.  Layer: facade and engines."""
from bench import program_spans as ps


def read(run):
    p = ps.program(run)
    return None if p is None else ps.span_ms_per_call(p, "sort.params")
