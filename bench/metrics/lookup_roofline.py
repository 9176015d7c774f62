"""The lookup kernel's share of its roofline: the least time any
implementation needs for the window's multi-gets (their least HBM bytes,
``bench/roofline.py``, over the chip's HBM bandwidth; the lookup is
memory-bound) over the kernel's device time."""

from bench import roofline
from bench.metrics.lookup_kernel_ms import kernel_ns


def read(run):
    k = kernel_ns(run)
    if k is None:
        return None
    nbytes = sum(roofline.multiget_least_bytes(b, run.loaded,
                                               run.cell.config)
                 for b in run.batches)
    least_s, _ = roofline.least_seconds(nbytes, run.device_kind)
    return 100.0 * least_s / (k[0] / 1e9)
