"""The trace-to-metrics reduction on synthetic events, and on one small
trace recorded here on the CPU."""

import time

import numpy as np
import pytest

from bench import trace as tr

KERNEL = ('%race_lookup.1 = (f32[4096,1,256]{2,1,0}, s32[4096,1,1]{2,1,0}) '
          'custom-call(s32[8192]{0} %reshape.5), '
          'custom_call_target="tpu_custom_call"')
SHARDED = ('%sharded_lookup_call.1 = (f32[251,192,256]{2,1,0}) custom-call('
           's32[96384]{0} %reshape.2), custom_call_target="tpu_custom_call"')
OTHER_KERNEL = ('%flash_attention.3 = f32[8,128]{1,0} custom-call(f32[8,128]'
                '{1,0} %p), custom_call_target="tpu_custom_call"')
COPY = '%copy.2 = s32[262139,1,8]{2,1,0} copy(s32[262139,1,8]{0,2,1} %b)'


def synthetic():
    """Two multi-gets of 100 ns each; the chip runs a copy and a kernel in
    the first, overlapping ops in the second, and one op outside both."""
    ops = [(COPY, 10, 20), (KERNEL, 30, 50),            # span 1: busy 30
           (KERNEL, 120, 150), (OTHER_KERNEL, 140, 160),  # span 2: busy 40
           (COPY, 250, 260)]                         # outside the spans
    host = [("multiget", 0, 100), ("multiget", 100, 200),
            ("H2D Dispatch", 55, 95), ("Transpose", 60, 90),
            ("np.asarray(jax.Array)", 160, 200)]
    return tr.Trace(device_ops={0: ops}, host=host)


def test_union_merges_overlaps_and_drops_empty():
    got = tr.union([(5, 9), (0, 3), (2, 4), (9, 12), (20, 20), (15, 18)])
    assert got.tolist() == [[0, 4], [5, 12], [15, 18]]
    assert tr.union([]).shape == (0, 2)


def test_covered_and_gaps_clip_to_the_window():
    m = tr.union([(0, 4), (5, 12), (15, 18)])
    assert tr.covered_ns(m, 2, 16) == 2 + 7 + 1
    assert tr.gaps(m, 2, 16).tolist() == [[4, 5], [12, 15]]
    assert tr.gaps(m, -3, 30).tolist() == [[-3, 0], [4, 5], [12, 15],
                                           [18, 30]]
    assert tr.gaps(tr.union([]), 0, 7).tolist() == [[0, 7]]


def test_busy_idle_and_host_less_device():
    t = synthetic()
    assert tr.spans(t, "multiget") == [(0, 100), (100, 200)]
    assert tr.busy_ns(t, 0, 200) == 10 + 20 + 40
    # each span's host share: its length less the union of ops inside it
    assert tr.host_minus_device_ns(t) == [70.0, 60.0]
    assert tr.window(t) == (0, 200)


def test_busy_is_averaged_over_chips():
    t = synthetic()
    t.device_ops[1] = [(KERNEL, 0, 200)]
    assert tr.busy_ns(t, 0, 200) == (70 + 200) / 2


def test_kernels_are_matched_by_name_and_target():
    t = synthetic()
    t.device_ops[0].append((SHARDED, 170, 180))
    got = tr.kernel_ops(t, ("race_lookup", "sharded_lookup_call"), 0, 200)
    assert [(tr.op_name(h), s, e) for h, s, e in got] == [
        ("race_lookup", 30, 50), ("race_lookup", 120, 150),
        ("sharded_lookup_call", 170, 180)]
    # a name that is not a tpu_custom_call (a copy) never matches
    assert tr.kernel_ops(t, ("copy",), 0, 300) == []


def test_op_labels():
    assert tr.op_name(KERNEL) == "race_lookup"
    assert tr.op_label(KERNEL) == "%race_lookup.1 custom-call"
    assert tr.op_label(COPY) == "%copy.2 copy"
    top = tr.top_ops(synthetic(), 0, 200)
    assert top[0] == ["%race_lookup.1 custom-call", pytest.approx(50e-9)]


def test_idle_gaps_are_labelled_by_host_activity():
    gaps = tr.idle_gaps(synthetic(), 0, 200)
    # longest first: 50-120 (70 ns), 160-200 (40 ns), 0-10, 20-30
    assert [g[1] for g in gaps] == pytest.approx(
        [70e-9, 40e-9, 10e-9, 10e-9])
    label = gaps[0][0]
    assert label.startswith("H2D Dispatch 0.57; Transpose 0.43;")
    assert label.endswith("no host event 0.43")
    assert gaps[1][0] == ("np.asarray(jax.Array) 1.00; "
                          "no host event 0.00")


def test_a_trace_recorded_on_the_cpu(tmp_path):
    """The loader finds the benchmark's spans in a real profiler trace;
    a CPU backend has no TPU plane, so nothing reads as device time."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x * 2).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    for _ in range(3):
        with jax.profiler.TraceAnnotation(tr.SPAN):
            np.asarray(f(x))
            time.sleep(0.01)
    jax.profiler.stop_trace()
    t = tr.load(next(tmp_path.rglob("*.xplane.pb")))
    s = tr.spans(t, tr.SPAN)
    assert len(s) == 3
    assert all(e - b >= 10_000_000 for b, e in s)
    assert s[0][1] <= s[1][0] <= s[1][1] <= s[2][0]
    assert t.device_ops == {}
    assert tr.window(t) == (s[0][0], s[-1][1])
    assert tr.busy_ns(t, s[0][0], s[-1][1]) == 0.0
    assert tr.kernel_ops(t, ("race_lookup",), s[0][0], s[-1][1]) == []


def test_the_trace_readers_on_a_synthetic_run():
    """The per-layer readers of bench/metrics on the synthetic trace: two
    multi-gets of 100 ns, kernels of 20 and 30 ns, chip busy 70 of 200."""
    import types

    from bench import roofline
    from bench import run as harness

    config = {"buckets": 787, "slots_per_bucket": 8, "vdim": 256}
    batch = np.array([3, 5, 8, 13])               # 4 loaded keys
    cell = types.SimpleNamespace(config=config)
    run = harness.Run(cell=cell, table=None, device_kind="TPU v5 lite",
                      setup_s=1.0, window_s=2e-7, latencies_s=[1e-7] * 2,
                      lookups=8, batches=[batch, batch],
                      loaded=np.arange(1, 20), compiles_in_window=0,
                      trace=synthetic())

    def read(name):
        return harness.load_module(
            harness.BENCH / "metrics" / f"{name}.py").read(run)

    assert read("lookup_kernel_ms") == pytest.approx(25e-6)
    assert read("device_idle_pct") == pytest.approx(65.0)
    assert read("host_ms_per_multiget") == pytest.approx(65e-6)
    rows = roofline.candidate_rows(batch, config)
    least = 2 * roofline.least_bytes(rows, 4, 4, nslot=8, vdim=256) / 819e9
    assert read("lookup_roofline") == pytest.approx(100 * least / 50e-9)
    assert read("lookups_per_s") == pytest.approx(4e7)
    assert read("multiget_p95_ms") == pytest.approx(1e-4)
    # with no trace the trace readers find nothing and return nothing
    run.trace = None
    for name in ("lookup_kernel_ms", "lookup_roofline", "device_idle_pct",
                 "host_ms_per_multiget"):
        assert read(name) is None
