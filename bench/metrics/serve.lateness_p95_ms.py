"""95th percentile of the generator's lateness in an open loop: how long
after its due time each request of the window was submitted (host clock).
A starved generator reads high here and is not read as a fast server.
Layer: harness loop."""
import numpy as np


def read(run):
    late = run.window.lateness
    return float(np.percentile(late, 95)) * 1e3 if late else None
