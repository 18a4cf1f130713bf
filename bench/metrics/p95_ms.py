"""95th percentile of the latency of every call completed in the window,
from the call of ``sort()`` to its return with host arrays (host clock,
numpy's linear interpolation between order statistics)."""
import numpy as np


def read(run):
    lat = [c.latency_s for c in run.done]
    return float(np.percentile(lat, 95)) * 1e3 if lat else None
