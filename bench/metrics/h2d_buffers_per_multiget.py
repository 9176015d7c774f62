"""Device buffers the program's host-to-device puts created per
multi-get: the table's own totals (``table.stats.h2d_buffers /
table.stats.calls``), each put counting the device shards of each array
it put. They count every ``lookup_batch`` the run's table served: the
window's and exactly one whole multi-get of set-up, so the one table
ship is spread over them. None where the table keeps no such count."""


def read(run):
    stats = getattr(run.table, "stats", None)
    if stats is None or not stats.calls or not hasattr(stats,
                                                         "h2d_buffers"):
        return None
    return stats.h2d_buffers / stats.calls
