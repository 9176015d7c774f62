"""Spans and counters (repro.obs) and the device RACE tables' use of them:
call ids, counts into the right stats, and the spans read back from a
profiler trace taken on the CPU."""

import dataclasses
from pathlib import Path

import numpy as np
import pytest

import jax

from repro import obs
from repro.kernels.race_lookup.race_lookup import group_by_shard
from repro.kernels.race_lookup.ref import pool_lookup_ref
from repro.kvs.race import (DeviceRaceTable, LookupStats, PoolRaceTable,
                             ShardedDeviceRaceTable)


@dataclasses.dataclass
class Counts:
    calls: int = 0
    keys: int = 0


def traced(tmp_path, fn):
    """Run ``fn`` under a profiler session; return its result and the
    host events whose name starts with ``race.`` or ``t.``, in time
    order, as (name, {arg: value})."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path = next(Path(tmp_path).rglob("*.xplane.pb"))
    events = []
    for plane in jax.profiler.ProfileData.from_file(str(path)).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("race.", "t.")):
                    events.append((e.start_ns, e.name, dict(e.stats)))
    return out, [(n, a) for _, n, a in sorted(events, key=lambda x: x[0])]


def test_nested_requests_keep_their_own_call_and_stats(tmp_path):
    outer, inner = Counts(), Counts()

    def work():
        with obs.request(outer):
            with obs.span("t.a", keys=3):
                pass
            with obs.request(inner):
                with obs.span("t.b", keys=5):
                    pass
            with obs.span("t.c", keys=7):
                pass
        with obs.span("t.d", keys=11):       # outside any request
            pass

    _, events = traced(tmp_path, work)
    assert [n for n, _ in events] == ["t.a", "t.b", "t.c", "t.d"]
    a, b, c, d = (args for _, args in events)
    assert a["call"] == c["call"] != b["call"]
    assert "call" not in d and d["keys"] == 11
    assert (outer.calls, outer.keys) == (1, 3 + 7)
    assert (inner.calls, inner.keys) == (1, 5)


def test_span_args_read_back_and_only_stats_fields_count(tmp_path):
    stats = LookupStats()

    def work():
        with obs.request(stats):
            with obs.span("t.x", h2d_bytes=123, variant="sharded") as add:
                add(slots=40, qcap=8)

    _, events = traced(tmp_path, work)
    [(name, args)] = events
    assert name == "t.x"
    assert args == {"call": args["call"], "h2d_bytes": 123,
                    "variant": "sharded", "slots": 40, "qcap": 8}
    # qcap and variant are no fields of the stats: arguments only
    assert stats == LookupStats(calls=1, h2d_bytes=123, slots=40)


def _by_call(events):
    """``events`` split into one list per request, in call order."""
    calls = {}
    for n, a in events:
        calls.setdefault(a["call"], []).append((n, a))
    return [calls[c] for c in sorted(calls)]


def test_spans_outside_a_trace_still_count():
    stats = LookupStats()
    for _ in range(3):
        with obs.request(stats):
            with obs.span("t.y", keys=2) as add:
                add(padded_slots=1)
    assert stats == LookupStats(calls=3, keys=6, padded_slots=3)


def _filled(table, keys, vdim, seed):
    rng = np.random.default_rng(seed)
    for k in keys:
        table.insert(int(k), rng.standard_normal(vdim).astype(np.float32))


@pytest.mark.parametrize("impl", ["pallas", "ref"])
def test_flat_table_spans_and_stats(tmp_path, impl):
    nb, nslot, vdim = 64, 8, 32
    table = DeviceRaceTable(n_buckets=nb, nslot=nslot, vdim=vdim)
    _filled(table, range(1, 200), vdim, 0)
    keys = np.arange(150, 250)
    answers, events = traced(tmp_path, lambda: [
        table.lookup_batch(keys, impl=impl) for _ in range(2)])
    for v, f in answers:
        assert np.asarray(f)[:50].all() and not np.asarray(f)[50:].any()
    calls = _by_call(events)
    # the first lookup ships the table in a span of its own; the second
    # finds it resident and ships the query operands alone
    assert [[n for n, _ in c] for c in calls] == [
        ["race.prep", "race.to_device", "race.to_device", "race.kernel"],
        ["race.prep", "race.to_device", "race.kernel"]]
    variant = "scalar" if impl == "pallas" else "ref"
    assert calls[0][3][1]["variant"] == calls[1][2][1]["variant"] == variant
    table_bytes = nb * nslot * 4 + nb * nslot * vdim * 4
    queries = len(keys) * (4 + 8)
    assert calls[0][1][1]["h2d_bytes"] == table_bytes
    assert calls[0][1][1]["table_ships"] == 1
    assert calls[0][2][1]["h2d_bytes"] == calls[1][1][1]["h2d_bytes"] \
        == queries
    assert "table_ships" not in calls[1][1][1]
    assert table.stats == LookupStats(calls=2, keys=2 * len(keys),
                                      h2d_bytes=table_bytes + 2 * queries,
                                      table_ships=1)


def test_sharded_table_spans_and_stats(tmp_path):
    ns, nb, nslot, vdim = 3, 32, 8, 32
    table = ShardedDeviceRaceTable(n_shards=ns, n_buckets=nb, nslot=nslot,
                                   vdim=vdim)
    _filled(table, range(1, 300), vdim, 1)
    batches = [np.arange(250, 350), np.arange(1, 41), np.arange(7, 8)]
    answers, events = traced(tmp_path, lambda: [
        table.lookup_batch(b) for b in batches[:2]])
    f = np.asarray(answers[0][1])
    assert f[:50].all() and not f[50:].any()
    calls = _by_call(events)
    # the first lookup ships the stacked tables before handing them out;
    # the second finds them resident
    hit = ["race.prep", "race.stack", "race.group", "race.to_device",
           "race.kernel"]
    assert [[n for n, _ in c] for c in calls] == [
        hit[:1] + ["race.to_device"] + hit[1:], hit]
    assert calls[0][1][1]["table_ships"] == 1
    assert all("table_ships" not in a for _, a in calls[1])
    assert calls[0][5][1]["variant"] == calls[1][4][1]["variant"] \
        == "sharded"
    table.lookup_batch(batches[2])

    # the same totals recomputed from the shapes and the grouping
    table_bytes = ns * nb * nslot * (4 + vdim * 4)
    assert calls[0][1][1]["h2d_bytes"] == table_bytes
    slots = 0
    h2d = table_bytes
    for b in batches:
        _, _, sidx = table.prep(b)
        _, _, pos, _ = group_by_shard(np.zeros(len(b), np.int32),
                                      np.zeros((len(b), 2), np.int32),
                                      sidx, ns, 64)
        assert pos.shape[0] == ns
        slots += pos.size
        h2d += pos.size * (4 + 8) + len(b) * 4
    keys = sum(len(b) for b in batches)
    assert table.stats == LookupStats(calls=3, keys=keys, h2d_bytes=h2d,
                                      table_ships=1, slots=slots,
                                      padded_slots=slots - keys)
    group = calls[0][3][1]
    assert group["slots"] - group["padded_slots"] == len(batches[0])
    assert group["slots"] == ns * group["qcap"]


def test_pool_table_spans_and_stats(tmp_path):
    nb, nslot, vdim = 64, 8, 32
    table = PoolRaceTable(n_buckets=nb, nslot=nslot, vdim=vdim,
                          capacity=256)
    _filled(table, range(1, 200), vdim, 2)
    keys = np.arange(150, 250)
    answers, events = traced(tmp_path, lambda: [
        table.lookup_batch(keys) for _ in range(2)])
    for v, f in answers:
        assert np.asarray(f)[:50].all() and not np.asarray(f)[50:].any()
    calls = _by_call(events)
    # the flat table's spans, in its order: the first lookup ships the
    # index and pool in a span of its own, the second finds them resident
    assert [[n for n, _ in c] for c in calls] == [
        ["race.prep", "race.to_device", "race.to_device", "race.kernel"],
        ["race.prep", "race.to_device", "race.kernel"]]
    assert calls[0][3][1]["variant"] == calls[1][2][1]["variant"] == "pool"
    table_bytes = sum(a.nbytes for a in table.tables())
    # the query operands go as one packed array: keys, fingerprints and
    # both buckets, 100 queries in a row of 128 lanes
    queries = 4 * 128 * 4
    assert calls[0][1][1]["h2d_bytes"] == table_bytes
    assert calls[0][1][1]["table_ships"] == 1
    assert calls[0][1][1]["h2d_buffers"] == 3     # index, keys, pool
    assert calls[0][2][1]["h2d_bytes"] == calls[1][1][1]["h2d_bytes"] \
        == queries
    assert calls[0][2][1]["h2d_buffers"] == calls[1][1][1]["h2d_buffers"] \
        == 1
    assert "table_ships" not in calls[1][1][1]
    # the blocks counted on the device: each lookup's fingerprint matches,
    # as the reference counts them from the same tables
    _, _, per_key = pool_lookup_ref(*table.tables(), *table.prep(keys),
                                    nslot=nslot)
    assert int(table.stats.blocks) == 2 * int(np.sum(per_key)) >= 2 * 50
    assert dataclasses.replace(table.stats, blocks=0) == LookupStats(
        calls=2, keys=2 * len(keys), h2d_bytes=table_bytes + 2 * queries,
        h2d_buffers=3 + 2, table_ships=1)
