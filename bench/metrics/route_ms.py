"""Host time per multi-get in grouping the queries per shard and
scattering the answers back to the keys' order (``race.group`` and
``race.scatter``), mean over the window's multi-gets, ms."""

from bench import spans


def read(run):
    return None if run.trace is None else spans.span_ms(
        run.trace, ["race.group", "race.scatter"])
