"""Mean self time per traced call, in ms, of the program's ``sort.encode``
spans: keys turned into bit planes (``fused_tns_sort``) or order-preserving
unsigned keys (``_unsigned_keys``) on the host.  Layer: facade and engines."""
from bench import program_spans as ps


def read(run):
    p = ps.program(run)
    return None if p is None else ps.span_ms_per_call(p, "sort.encode")
