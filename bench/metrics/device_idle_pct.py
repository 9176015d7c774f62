"""Share of the traced window in which no operation ran on the chip:
1 - (union of the ``XLA Ops`` intervals) / window, averaged over chips."""

from bench import trace as tr


def read(run):
    if run.trace is None or not run.trace.device_ops:
        return None
    w = tr.window(run.trace)
    if w is None:
        return None
    return 100.0 * (1.0 - tr.busy_ns(run.trace, *w) / (w[1] - w[0]))
