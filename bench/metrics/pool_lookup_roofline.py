"""The pool lookup kernel's share of its roofline: the least time any
implementation needs for the window's multi-gets (their least HBM bytes
read as RACE reads, ``bench/pool_roofline.py``, over the chip's HBM
bandwidth) over the kernel's device time. None where the trace holds no
such kernel or the table holds no pool-layout index."""

from bench import pool_roofline, roofline
from bench.metrics.pool_lookup_kernel_ms import kernel_ns


def read(run):
    k = kernel_ns(run)
    tables = getattr(run.table, "tables", None)
    if k is None or tables is None:
        return None
    config = run.cell.config
    slots = pool_roofline.decode(tables()[0], config["buckets"],
                                 config["slots_per_bucket"])
    nbytes = sum(pool_roofline.multiget_least_bytes(b, run.loaded, slots,
                                                    config)
                 for b in run.batches)
    least_s, _ = roofline.least_seconds(nbytes, run.device_kind)
    return 100.0 * least_s / (k[0] / 1e9)
