"""Device time per multi-get of the pool-layout lookup kernel (the
two-level RACE read of ``PoolRaceTable``). The trace names the Pallas
kernel by the HLO instruction of its ``tpu_custom_call``, which takes
the name of the jitted wrapper that calls it, ``pool_lookup``."""

from bench import trace as tr

KERNELS = ("pool_lookup",)


def kernel_ns(run):
    """(kernel ns in the window averaged over chips, multi-gets traced),
    or None where the trace holds no such kernel, as a program without
    the pool layout gives."""
    w = None if run.trace is None else tr.window(run.trace)
    if w is None:
        return None
    ops = tr.kernel_ops(run.trace, KERNELS, *w)
    if not ops:
        return None
    return (sum(e - b for _, b, e in ops) / len(run.trace.device_ops),
            len(tr.spans(run.trace, tr.SPAN)))


def read(run):
    k = kernel_ns(run)
    return None if k is None else k[0] / k[1] / 1e6
