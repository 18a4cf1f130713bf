"""Share of the traced window's device-idle time, in %, during which the host
was in no child span of the program's ``sort`` spans and not in the
harness's ``between_calls``: device idle time that no span explains.  Layer:
facade and engines."""
from bench import program_spans as ps


def read(run):
    p = ps.program(run)
    return None if p is None else ps.idle_unattributed_pct(p, run.trace)
