"""Mean self time per traced call, in ms, of the program's ``sort.finish``
spans: ``_finish``'s slice, value gather and ``SortResult``.  Layer: facade
and engines."""
from bench import program_spans as ps


def read(run):
    p = ps.program(run)
    return None if p is None else ps.span_ms_per_call(p, "sort.finish")
