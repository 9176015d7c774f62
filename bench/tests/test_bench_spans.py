"""The readers of the program's own spans and counters (bench/spans.py and
the metrics that use it) on synthetic traces and tables."""

import types

import numpy as np
import pytest

from bench import run as harness
from bench import spans
from bench import trace as tr

KERNEL = ('%sharded_lookup_call.1 = (f32[3,64,256]{2,1,0}) custom-call('
          's32[384]{0} %reshape.2), custom_call_target="tpu_custom_call"')


def synthetic():
    """Two sharded multi-gets of 100 ns, each phase in its own span; the
    kernel runs while the host waits in ``race.to_host``; the host copies
    the answers again after the program's spans (``np.asarray``)."""
    host = [("multiget", 0, 100), ("multiget", 100, 200),
            ("race.prep", 5, 15), ("race.stack", 15, 35),
            ("race.group", 35, 40), ("race.to_device", 40, 60),
            ("race.kernel", 60, 62), ("race.to_host", 62, 80),
            ("race.scatter", 80, 85), ("race.to_device", 85, 90),
            ("np.asarray(jax.Array)", 90, 98),
            ("race.prep", 105, 110), ("race.stack", 110, 130),
            ("race.group", 130, 132), ("race.to_device", 132, 150),
            ("race.kernel", 150, 151), ("race.to_host", 151, 170),
            ("race.scatter", 170, 180), ("race.to_device", 180, 185)]
    ops = [(KERNEL, 60, 75), (KERNEL, 155, 165)]
    return tr.Trace(device_ops={0: ops}, host=host)


def read(name, run):
    return harness.load_module(
        harness.BENCH / "metrics" / f"{name}.py").read(run)


#: (metric, mean over the two multi-gets in ns)
SPAN_METRICS = [
    ("prep_ms", (10 + 5) / 2),
    ("stack_ms", (20 + 20) / 2),
    ("route_ms", ((5 + 5) + (2 + 10)) / 2),
    ("to_device_ms", ((20 + 5) + (18 + 5)) / 2),
    # to_host less the kernel inside it: 18 - 13 and 19 - 10
    ("to_host_ms", (5 + 9) / 2),
    # 100 less the spans 5-90 (the kernel lies inside them), and 100
    # less 105-185
    ("unspanned_host_ms", (15 + 20) / 2),
]


@pytest.mark.parametrize("name,ns", SPAN_METRICS)
def test_span_readers_on_a_synthetic_trace(name, ns):
    run = types.SimpleNamespace(trace=synthetic(), table=None)
    assert read(name, run) == pytest.approx(ns / 1e6)


def test_the_spans_and_what_they_miss_add_up_to_the_host_time():
    t = synthetic()
    total = sum(spans.span_less_device_ms(t, [n]) for n in (
        "race.prep", "race.stack", "race.group", "race.to_device",
        "race.kernel", "race.to_host", "race.scatter"))
    host = np.mean(tr.host_minus_device_ns(t)) / 1e6
    assert total + spans.unspanned_ms(t) == pytest.approx(host)


def test_without_a_device_the_spans_count_whole():
    """On a trace with no chip (the CPU), nothing is device time."""
    t = synthetic()
    t.device_ops = {}
    assert spans.span_less_device_ms(t, ["race.to_host"]) == pytest.approx(
        (18 + 19) / 2 / 1e6)
    assert spans.unspanned_ms(t) == pytest.approx((15 + 20) / 2 / 1e6)


def test_device_time_is_averaged_over_chips():
    t = synthetic()
    t.device_ops[1] = []                      # a second chip, idle
    assert spans.span_less_device_ms(t, ["race.to_host"]) == pytest.approx(
        ((5 + 18) / 2 + (9 + 19) / 2) / 2 / 1e6)


@pytest.mark.parametrize("name", [m for m, _ in SPAN_METRICS])
def test_span_readers_find_nothing_without_the_programs_spans(name):
    """A program without these spans (an earlier commit) reads nothing,
    and so does a run without a trace."""
    t = synthetic()
    t.host = [h for h in t.host if not h[0].startswith("race.")]
    assert read(name, types.SimpleNamespace(trace=t, table=None)) is None
    assert read(name, types.SimpleNamespace(trace=None, table=None)) is None


def _table(**stats):
    fields = dict(calls=0, keys=0, h2d_bytes=0, slots=0, padded_slots=0)
    fields.update(stats)
    return types.SimpleNamespace(stats=types.SimpleNamespace(**fields))


def test_counter_readers():
    table = _table(calls=4, keys=4 * 4096, h2d_bytes=4 * 2_155_880_000,
                   slots=4 * 251 * 192, padded_slots=4 * (251 * 192 - 4096))
    run = types.SimpleNamespace(trace=None, table=table)
    assert read("h2d_mb_per_multiget", run) == pytest.approx(2155.88)
    assert read("shard_pad_pct", run) == pytest.approx(
        100 * (1 - 4096 / (251 * 192)))


def test_counter_readers_find_nothing_without_the_counts():
    for table in (object(), _table(), _table(calls=2, h2d_bytes=10)):
        run = types.SimpleNamespace(trace=None, table=table)
        assert read("shard_pad_pct", run) is None
    assert read("h2d_mb_per_multiget",
                types.SimpleNamespace(trace=None, table=object())) is None
    assert read("h2d_mb_per_multiget",
                types.SimpleNamespace(trace=None, table=_table())) is None
