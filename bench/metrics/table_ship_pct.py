"""Share of multi-gets that shipped the whole table to the device, in %:
``100 x table.stats.table_ships / table.stats.calls``, the table's own
totals. They count every ``lookup_batch`` the run's table served: the
window's and exactly one whole multi-get of set-up, drawn from the same
traffic. None where the table keeps no such count, as a program whose
table is copied on every call has none."""


def read(run):
    stats = getattr(run.table, "stats", None)
    if stats is None or not stats.calls or not hasattr(stats,
                                                         "table_ships"):
        return None
    return 100.0 * stats.table_ships / stats.calls
