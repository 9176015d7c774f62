"""KV blocks the pool kernel fetched per key asked for: the table's own
totals, ``table.stats.blocks / table.stats.keys``. Every found key costs
one block, and each false 8-bit fingerprint match one more, so a table
that finds its keys reads 1 and above by the false-match rate. They count
every ``lookup_batch`` the run's table served: the window's and exactly
one whole multi-get of set-up, drawn from the same traffic. None where
the table keeps no such count."""


def read(run):
    stats = getattr(run.table, "stats", None)
    blocks = getattr(stats, "blocks", None)
    if blocks is None or not stats.keys or not int(blocks):
        return None
    return int(blocks) / stats.keys
