"""Mean host time per multi-get: the length of the benchmark's span
around each multi-get less the device-busy time inside it, on the
profiler's clock. It holds hashing, routing, grouping, the table copies
to the device, the answers' copy back and the scatter."""

import numpy as np

from bench import trace as tr


def read(run):
    if run.trace is None:
        return None
    host = tr.host_minus_device_ns(run.trace)
    return float(np.mean(host)) / 1e6 if host else None
