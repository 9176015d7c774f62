"""A rehearsal of the benchmark on the CPU: every cell of BENCHMARK.json,
resolved by name and run end to end through the harness's cell runner at
a tiny table size (Pallas in interpret mode), its check with the timed
path broken underneath, and the control that has to come out incorrect.
The command itself refuses a CPU; that is checked too."""

import functools
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from bench import control
from bench import run as harness

ROOT = Path(harness.__file__).resolve().parents[1]
SPEC = harness.load_spec()
CELLS = [w["name"] for w in SPEC["workloads"]]
#: a few thousand records instead of a million; widths as configured
TINY = {"config": {"recordcount": 3000, "n_shards": 3,
                   "buckets_per_shard": 257, "buckets": 787},
        "traffic": {"multiget_keys": 64}}
SEED = 2 ** 31 + 11


def tiny(name):
    return harness.resolve(SPEC, name, TINY)


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("name", CELLS)
def test_every_cell_runs_and_is_correct(name, trace):
    cell = tiny(name)
    out = harness.run_cell(cell, SEED, 0.3, bool(trace),
                           t_start=time.perf_counter())
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(out)[-1] == "checks"
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0 and out["attempted"] % 64 == 0
    assert out["answers_checked"] == out["attempted"]
    assert out["checks"] == {"mismatched_answers": {"value": 0, "limit": 0}}
    dev = out["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)
    wanted = cell.per_layer if trace else cell.end_to_end
    names = {m["name"] for m in wanted}
    assert set(out["metrics"]) <= names
    for m in wanted:
        if m["name"] in out["metrics"]:
            assert out["metrics"][m["name"]]["unit"] == m["unit"]
    if trace:
        assert dev["window_s"] > 0 and "busy_s" in dev
        assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
        # host clock and counter readings exist on any backend
        assert {"host_ms_per_multiget", "compiles_in_window"} <= set(
            out["metrics"])
        assert out["metrics"]["compiles_in_window"]["value"] == 0
    else:
        assert names == set(out["metrics"])
        assert all(v["value"] > 0 for v in out["metrics"].values())
    json.dumps(out)


def _alter_one_answer(v, f):
    v = np.array(v)
    v.view(np.uint32)[len(v) // 2, 3] ^= 1
    return v, f


def _drop_half(v, f):
    v, f = np.array(v), np.array(f)
    v[len(v) // 2:] = 0
    f[len(f) // 2:] = 0
    return v, f


def _misplace(v, f):
    return np.roll(np.asarray(v), 1, axis=0), np.roll(np.asarray(f), 1)


@pytest.mark.parametrize("fault", [_alter_one_answer, _drop_half,
                                   _misplace])
@pytest.mark.parametrize("name", ["sharded-ycsbc-zipf", "flat-ycsbc-zipf"])
def test_a_broken_timed_path_is_not_correct(name, fault, monkeypatch):
    """Break the program's lookup where the answers are produced (the
    kernel wrapper under ``lookup_batch``): the run comes out incorrect."""
    from repro.kernels.race_lookup import ops

    entry = "race_lookup_sharded" if "sharded" in name else "race_lookup"
    real = getattr(ops, entry)
    monkeypatch.setattr(ops, entry, functools.wraps(real)(
        lambda *a, **k: fault(*real(*a, **k))))
    out = harness.run_cell(tiny(name), SEED, 0.2, False,
                           t_start=time.perf_counter())
    assert out["correct"] is False
    assert out["failed"] == out["checks"]["mismatched_answers"]["value"] > 0


def test_the_bfloat16_control_is_not_correct():
    lines = list(control.run(tiny("flat-ycsbc-zipf"), [SEED, SEED + 1],
                             0.2))
    assert len(lines) == 2
    for line in lines:
        assert line["correct"] is False
        # rounding to bfloat16 changes nearly every 1 KB record
        assert line["mismatched_answers"] > 0.9 * line["answers_checked"]


def _command(cwd, *args):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "1", "--seconds", "1", "--trace", "0", *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=120)


def test_the_command_refuses_a_cpu():
    p = _command(ROOT)
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_the_command_needs_the_program(tmp_path):
    """A checkout with only BENCHMARK.json and the benchmark's files has
    no system to measure: the command fails and prints no result."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _command(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
