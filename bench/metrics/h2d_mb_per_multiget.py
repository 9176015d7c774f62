"""Bytes the program copied from the host to the device per multi-get,
in MB (1e6 B): the table's own totals (``table.stats.h2d_bytes /
table.stats.calls``). They count every ``lookup_batch`` the run's table
served: the window's and exactly one whole multi-get of set-up, drawn
from the same traffic. None where the table keeps no such totals."""


def read(run):
    stats = getattr(run.table, "stats", None)
    if stats is None or not stats.calls:
        return None
    return stats.h2d_bytes / stats.calls / 1e6
