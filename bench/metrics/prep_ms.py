"""Host time per multi-get in the program's per-key hashing and shard
routing: the ``race.prep`` span of ``lookup_batch``, mean over the
window's multi-gets, ms."""

from bench import spans


def read(run):
    return None if run.trace is None else spans.span_ms(run.trace,
                                                        ["race.prep"])
