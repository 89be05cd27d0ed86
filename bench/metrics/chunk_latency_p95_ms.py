"""95th percentile over all chunks of the window of a chunk's latency:
from its arrival take to the next take, or to the loop's return for the
last chunk (the wall time the online serve adds to every result)."""
import numpy as np


def read(run):
    ends = run.stamps[1:] + [run.t_end]
    lat = np.subtract(ends, run.stamps)
    return float(np.percentile(lat, 95)) * 1e3
