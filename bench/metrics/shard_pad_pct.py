"""Share of the sharded kernel's query slots that are padding: every
shard is padded to the busiest one's tile-rounded count (QCAP), so
100 x padded slots / slots, from the table's own totals
(``table.stats``). They count every ``lookup_batch`` the run's table
served: the window's and exactly one whole multi-get of set-up, drawn
from the same traffic. None where the table keeps no such totals."""


def read(run):
    stats = getattr(run.table, "stats", None)
    if stats is None or not stats.slots:
        return None
    return 100.0 * stats.padded_slots / stats.slots
