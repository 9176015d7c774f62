"""Host time per multi-get in stacking the per-shard tables into one
array (``race.stack``, around ``ShardedDeviceRaceTable.tables()``), mean
over the window's multi-gets, ms."""

from bench import spans


def read(run):
    return None if run.trace is None else spans.span_ms(run.trace,
                                                        ["race.stack"])
