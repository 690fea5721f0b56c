"""95th percentile, in ms, of plan latency over every request of the
window."""
import numpy as np


def read(out):
    return float(np.percentile(np.asarray(out.latencies_s, np.float64),
                               95)) * 1e3
