"""95th percentile, in ms, of chunk latency over every chunk of the
window: from the moment the engine asks for the chunk until its state is
ready and its meter record written."""
import numpy as np


def read(out):
    return float(np.percentile(np.asarray(out.latencies_s, np.float64),
                               95)) * 1e3
