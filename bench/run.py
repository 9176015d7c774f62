"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix and its metrics are found by
name: ``BENCHMARK.json`` at the checkout's root names them, and
``bench/configs/<config>.json``, ``bench/traffic/<mix>.json`` and
``bench/metrics/<metric>.py`` hold them. A configuration names the system
adapter (``bench/systems/<system>.py``) that builds the program's table
and serves a multi-get through the program's own entry, and the plain
reference (``bench/references/<reference>.py``) that its answers are
checked against.

One run: make the records from ``--seed``, load them through the
program's ``insert``, warm up every shape the traffic can bring (all of
that is ``setup_s``), then drive one closed-loop client for
``--seconds``, each multi-get drawn afresh from a generator seeded from
``--seed``: the window ends when the first multi-get that finishes after
that time has finished. With ``--trace 1`` the window is traced by
the JAX profiler and the per-layer metrics are read from the trace;
otherwise the end-to-end metrics are reported. After the window every
retained answer is compared with the reference, and the last line of
standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` when traced), then
``checks``, each number compared with its limit.

The command refuses a backend that is not a TPU, or fewer chips than the
cell asks for: it then exits 2 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import roofline, trace as tr  # noqa: E402

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: multi-gets drawn in set-up, apart from the window's, to find the
#: shapes the traffic brings
WARM_SAMPLE = 64
#: answers kept for the check after the window: a reservoir sample drawn
#: from the seed once more than this many bytes of answers came back
KEEP_ANSWER_BYTES = 1 << 30


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}".replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_spec() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with everything it names resolved."""
    name: str
    chips: int
    config: dict
    traffic: dict
    generator: object
    system: object
    reference: object
    end_to_end: list[dict]
    per_layer: list[dict]


def resolve(spec: dict, name: str, overrides: dict | None = None) -> Cell:
    """Find a cell's configuration, traffic mix, system, reference and
    metrics by the names ``BENCHMARK.json`` gives. ``overrides`` updates
    the configuration and the mix (the CPU tests shrink them)."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: "
                         f"{sorted(cells)}")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = json.loads((ROOT / cfg_entry["file"]).read_text())
    traffic = json.loads((BENCH / "traffic" / f"{w['traffic']}.json")
                         .read_text())
    overrides = overrides or {}
    config.update(overrides.get("config", {}))
    traffic.update(overrides.get("traffic", {}))

    def reported(m):
        return "workloads" not in m or name in m["workloads"]

    return Cell(
        name=name, chips=w["chips"], config=config, traffic=traffic,
        generator=load_module(
            BENCH / "traffic" / f"{traffic['generator']}.py"),
        system=load_module(BENCH / "systems" / f"{config['system']}.py"),
        reference=load_module(
            BENCH / "references" / f"{config['reference']}.py"),
        end_to_end=[m for m in spec["end_to_end"] if reported(m)],
        per_layer=[m for m in spec["per_layer"] if reported(m)])


def make_records(config: dict, rng) -> tuple[np.ndarray, np.ndarray]:
    """``recordcount`` distinct keys in [1, 2^30) and their records: each
    ``vdim`` x 4 bytes of printable characters, as YCSB's fields are,
    viewed as the float32 rows the table stores (always finite)."""
    n, vdim = config["recordcount"], config["vdim"]
    keys = rng.choice(2 ** 30 - 1, n, replace=False) + 1
    words = rng.integers(0, 2 ** 32, (n, vdim), dtype=np.uint32)
    words &= np.uint32(0x3F3F3F3F)
    words += np.uint32(0x30303030)          # every byte in '0'..'o'
    return keys, words.view(np.float32)


@dataclasses.dataclass
class Run:
    """What one run measured; each metric reader takes what it needs."""
    cell: Cell
    table: object
    device_kind: str
    setup_s: float
    window_s: float
    latencies_s: list[float]
    lookups: int
    #: the keys of every multi-get of the window, in order
    batches: list[np.ndarray]
    #: every key the table holds, sorted
    loaded: np.ndarray
    compiles_in_window: int
    trace: tr.Trace | None = None


class CompileCounter:
    """Counts JAX backend compilations (compiles and persistent-cache
    loads) by the host clock at which each finished."""

    def __init__(self):
        self.times: list[float] = []

    def __call__(self, event, duration, **kwargs):
        if event == COMPILE_EVENT:
            self.times.append(time.perf_counter())

    def between(self, lo: float, hi: float) -> int:
        return sum(lo <= t <= hi for t in self.times)


def _bits(a: np.ndarray) -> np.ndarray:
    return np.ascontiguousarray(a, np.float32).view(np.uint32)


def check(cell: Cell, keys, values, kept) -> tuple[int, dict]:
    """Compare every kept answer with the plain reference built from the
    inserted records: a KV store returns the written bytes exactly, so
    one differing bit or found flag is a mismatch."""
    ref = cell.reference.build(keys, values)
    mismatched = checked = 0
    for batch, v, f in kept:
        ev, ef = ref.get_many(batch)
        bad = (np.asarray(f) != 0) != ef
        bad |= np.any(_bits(v) != _bits(ev), axis=1)
        mismatched += int(bad.sum())
        checked += len(bad)
    return checked, {"mismatched_answers": {"value": mismatched,
                                            "limit": 0}}


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float = T_START) -> dict:
    """Set up, measure and check one run of ``cell``; returns the result
    line as a dict. The caller has checked the device."""
    import jax

    devices = jax.devices()
    device_kind = devices[0].device_kind
    t = time.perf_counter()
    keys, values = make_records(cell.config, np.random.default_rng(seed))
    n_keys = len(keys)

    def draw(rng, count):
        return keys[cell.generator.draw(cell.traffic, n_keys, rng, count)]

    t_records = time.perf_counter() - t
    t = time.perf_counter()
    table = cell.system.build(cell.config, keys, values)
    t_build = time.perf_counter() - t

    # warm up every shape the traffic can bring, found on a sample drawn
    # apart from the window's requests, so that nothing compiles inside
    # the window
    t = time.perf_counter()
    shapes = cell.system.warm_up(
        table, list(draw(np.random.default_rng([seed, 3]), WARM_SAMPLE)))
    print(f"set-up: records {t_records:.3f} s, inserts {t_build:.3f} s, "
          f"{len(shapes)} shape(s) {shapes} warmed up in "
          f"{time.perf_counter() - t:.3f} s", file=sys.stderr)

    counter = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)
    trace_dir = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)

    keep_n = max(1, KEEP_ANSWER_BYTES // (cell.traffic["multiget_keys"]
                                          * (cell.config["vdim"] * 4 + 4)))
    keep_rng = np.random.default_rng([seed, 1])
    traffic_rng = np.random.default_rng([seed, 2])
    kept, latencies, batches = [], [], []
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    deadline = t0 + seconds
    i = 0
    while True:
        batch = draw(traffic_rng, 1)[0]
        ts = time.perf_counter()
        with jax.profiler.TraceAnnotation(tr.SPAN):
            v, f = cell.system.multiget(table, batch)
        te = time.perf_counter()
        latencies.append(te - ts)
        batches.append(batch)
        if len(kept) < keep_n:
            kept.append((batch, v, f))
        else:
            j = int(keep_rng.integers(0, i + 1))
            if j < keep_n:
                kept[j] = (batch, v, f)
        i += 1
        if te >= deadline:
            break
    t1 = time.perf_counter()
    jax.monitoring.unregister_event_duration_listener(counter)

    run = Run(cell=cell, table=table, device_kind=device_kind,
              setup_s=setup_s, window_s=t1 - t0, latencies_s=latencies,
              lookups=sum(len(b) for b in batches), batches=batches,
              loaded=np.sort(keys),
              compiles_in_window=counter.between(t0, t1))
    if trace:
        jax.profiler.stop_trace()
        try:
            run.trace = tr.load(next(Path(trace_dir).rglob("*.xplane.pb")))
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)

    stats = [d.memory_stats() or {} for d in devices[:cell.chips]]
    device = {"platform": devices[0].platform, "kind": device_kind,
              "count": len(devices),
              "memory_peak_bytes": max(int(s.get("peak_bytes_in_use", 0))
                                       for s in stats)}
    metrics_spec = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in metrics_spec:
        reader = load_module(BENCH / "metrics" / f"{m['name']}.py")
        value = reader.read(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    out = {"correct": None, "attempted": run.lookups, "failed": None,
           "metrics": metrics, "device": device}
    if trace:
        lo, hi = tr.window(run.trace) or (0, 0)
        out["device"]["busy_s"] = tr.busy_ns(run.trace, lo, hi) / 1e9
        out["device"]["window_s"] = (hi - lo) / 1e9
        out["breakdown"] = {
            "device_ops": tr.top_ops(run.trace, lo, hi),
            "idle_gaps": tr.idle_gaps(run.trace, lo, hi)}

    del table, run
    out["answers_checked"], checks = check(cell, keys, values, kept)
    out["failed"] = checks["mismatched_answers"]["value"]
    out["correct"] = all(c["value"] <= c["limit"] for c in checks.values())
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = resolve(load_spec(), args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} TPU chip(s); JAX "
              f"sees {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    if args.trace:
        roofline.peaks(devices[0].device_kind)   # an unknown chip fails
    print(f"start-up: imports and JAX's client "
          f"{time.perf_counter() - T_START:.3f} s", file=sys.stderr)
    # the program's persistent compilation cache (a fixed directory in the
    # checkout, or JAX_COMPILATION_CACHE_DIR), holding every program
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    out = run_cell(cell, args.seed % 2 ** 64, args.seconds,
                   bool(args.trace))
    print(f"answers checked: {out['answers_checked']}", file=sys.stderr)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
