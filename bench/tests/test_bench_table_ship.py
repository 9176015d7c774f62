"""The reader of ``table_ship_pct``: the share of multi-gets that shipped
the whole table, from the table's own counts, and nothing where the
table keeps no such count."""

import types

import numpy as np
import pytest

from bench import run as harness
from repro.kvs.race import DeviceRaceTable

READER = harness.load_module(harness.BENCH / "metrics" / "table_ship_pct.py")


def _run(table):
    return types.SimpleNamespace(trace=None, table=table)


def _stats(**fields):
    return types.SimpleNamespace(stats=types.SimpleNamespace(**fields))


def test_the_share_of_calls_that_shipped_the_table():
    assert READER.read(_run(_stats(calls=2000, table_ships=1))) \
        == pytest.approx(0.05)
    assert READER.read(_run(_stats(calls=4, table_ships=4))) == 100.0


@pytest.mark.parametrize("table", [
    object(),                                   # no stats at all
    _stats(calls=0, table_ships=0),             # never served a lookup
    # a table that copies itself on every call keeps no such count
    _stats(calls=5, keys=20, h2d_bytes=10, slots=0, padded_slots=0)])
def test_nothing_to_read(table):
    assert READER.read(_run(table)) is None


def test_read_from_a_table_that_served_lookups():
    table = DeviceRaceTable(n_buckets=16, nslot=8, vdim=4)
    table.insert(3, np.ones(4, np.float32))
    for _ in range(3):
        table.lookup_batch(np.array([3, 4]))
    table.insert(5, np.ones(4, np.float32))
    table.lookup_batch(np.array([5]))
    assert READER.read(_run(table)) == pytest.approx(100.0 * 2 / 4)
