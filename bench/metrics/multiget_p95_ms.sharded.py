"""The multi-get tail where the window holds too few requests to bound it
end to end: the same reading as ``multiget_p95_ms``, from the traced run."""

import numpy as np


def read(run):
    return float(np.percentile(run.latencies_s, 95)) * 1e3
