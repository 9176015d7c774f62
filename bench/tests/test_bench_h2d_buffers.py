"""The reader of ``h2d_buffers_per_multiget`` on synthetic tables, as
``test_bench_spans.py`` reads ``h2d_mb_per_multiget``: the table's own
count of device buffers per call, and nothing where the table keeps no
such count (a program older than the count, or no table)."""

import types

import pytest

from bench import run as harness


def read(run):
    return harness.load_module(
        harness.BENCH / "metrics" / "h2d_buffers_per_multiget.py").read(run)


def _run(**stats):
    fields = dict(calls=0, keys=0, h2d_bytes=0, h2d_buffers=0)
    fields.update(stats)
    return types.SimpleNamespace(trace=None, table=types.SimpleNamespace(
        stats=types.SimpleNamespace(**fields)))


@pytest.mark.parametrize("calls, buffers, want", [
    (6740, 4 * 6740 + 12, 4 + 12 / 6740),    # four chips, one ship of 4 x 3
    (5000, 5000 + 3, 1 + 3 / 5000),          # one chip, one ship of 3
    (3, 0, 0.0)])
def test_buffers_per_call(calls, buffers, want):
    assert read(_run(calls=calls, h2d_buffers=buffers)) == pytest.approx(
        want)


def test_nothing_to_read_without_the_count():
    older = types.SimpleNamespace(calls=4, keys=16, h2d_bytes=10)
    for run in (types.SimpleNamespace(trace=None, table=object()),
                types.SimpleNamespace(trace=None, table=None),
                types.SimpleNamespace(
                    trace=None, table=types.SimpleNamespace(stats=older)),
                _run()):                                  # no call yet
        assert read(run) is None
