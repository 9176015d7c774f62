"""95th percentile of every multi-get in the window, from the moment it
is sent to its answers on the host (numpy's linear interpolation between
order statistics)."""

import numpy as np


def read(run):
    return float(np.percentile(run.latencies_s, 95)) * 1e3
