"""95th percentile of the latency of every call completed in the window,
from the call of ``sort()`` to its return with host arrays (host clock,
numpy's linear interpolation between order statistics).  In an open loop,
of every request of the schedule, from its due time: one that got no
answer counts as waiting until the window's end (``Run.latencies``)."""
import numpy as np


def read(run):
    lat = run.latencies
    return float(np.percentile(lat, 95)) * 1e3 if lat else None
