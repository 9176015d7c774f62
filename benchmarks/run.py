"""Benchmark harness: one function per paper table/figure + the roofline
table from the dry-run artifacts. Prints ``name,us_per_call,derived`` CSV.

    PYTHONPATH=src python -m benchmarks.run            # everything
    PYTHONPATH=src python -m benchmarks.run --only fig8
    PYTHONPATH=src python -m benchmarks.run --smoke    # CI: tiny shapes
                                                       # (interpret mode
                                                       # on a CPU)
"""

from __future__ import annotations

import argparse
import os
import sys
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def _smoke() -> None:
    """Tiny-shape regression gate for the batched data plane AND the
    serverless subsystem: runs in seconds on any host (interpret mode on
    a CPU) and fails loudly if a gated path regresses. No files are
    written."""
    from benchmarks.batched_lookup import run_suite

    results = run_suite(smoke=True)
    # explicit raises, not asserts: the gate must survive python -O
    for row in results["kernel_sweep"]:
        # tiny batches amortize nothing; gate only where tiling can win
        if row["batch"] >= 8 * row["qblock"] and row["speedup"] <= 1.0:
            raise SystemExit(f"tiled kernel regressed: {row}")
        print(f"smoke/kernel_b{row['batch']}_v{row['vdim']},"
              f"{row['tiled_us']:.3f},speedup={row['speedup']}x")
    for name in ("fabric_qpush_batch", "kv_lookup_many"):
        r = results[name]
        if r["speedup"] <= 1.0:
            raise SystemExit(f"{name} regressed: {r}")
        print(f"smoke/{name},{r['batched_us']:.3f},"
              f"speedup={r['speedup']}x")

    # idle-poll gate: a blocked single-op caller (one-sided READ, and a
    # two-sided call parked on a listener round trip) must issue ZERO
    # unproductive pops — the notify-driven reactor's whole point
    ns = results["notify_single_op"]
    if ns["read_idle_polls"] != 0 or ns["call_idle_polls"] != 0:
        raise SystemExit(
            f"idle-poll gate failed: blocked single-op caller issued "
            f"read={ns['read_idle_polls']} call={ns['call_idle_polls']} "
            f"idle pops (want 0): {ns}")
    # latency gate: notify-driven single-op READ p50 no worse than the
    # polled (qpop_block tick) baseline
    if ns["notify_p50_us"] > ns["polled_p50_us"] * 1.0001:
        raise SystemExit(
            f"notify latency gate failed: p50 {ns['notify_p50_us']}us > "
            f"polled baseline {ns['polled_p50_us']}us: {ns}")
    print(f"smoke/notify_single_op,{ns['notify_p50_us']:.3f},"
          f"polled={ns['polled_p50_us']}us_idle_polls=0")

    # session-vs-raw overhead gate: the typed Session/Future layer must
    # cost <= 5% added latency over hand-rolled qpush_batch at batch >= 128
    fb = results["fabric_qpush_batch"]
    if fb["n_wrs"] >= 128 and fb["session_overhead"] > 0.05:
        raise SystemExit(
            f"session layer overhead {100 * fb['session_overhead']:.1f}% "
            f"> 5% gate at batch {fb['n_wrs']}: {fb}")
    print(f"smoke/session_overhead,{fb['session_us_per_wr']:.3f},"
          f"overhead={100 * fb['session_overhead']:.2f}%_vs_raw_batched")

    # serverless: Fig 12b transfer-latency gate + doorbells-per-hop gate
    from benchmarks.serverless import check_gates
    from benchmarks.serverless import run_suite as serverless_suite

    sl = serverless_suite(smoke=True)
    bad = check_gates(sl)
    if bad:
        raise SystemExit("; ".join(bad))
    for row in sl["transfer"]:
        print(f"smoke/serverless_transfer_{row['nbytes']}B,"
              f"{row['krcore_us']:.3f},"
              f"reduction={100 * row['reduction_vs_verbs']:.1f}%")
    for row in sl["chain"]:
        print(f"smoke/serverless_chain_k{row['k']},"
              f"{row['krcore_transfer_us']:.3f},"
              f"doorbells={row['krcore_doorbells_per_hop']}/"
              f"{row['doorbell_budget_per_hop']}")
    ru = sl["chain_reuse"]
    print(f"smoke/serverless_chain_reuse,{ru['epoch_control_us'][-1]},"
          f"control_saved={100 * ru['reuse_reduction']:.1f}%")
    rp = sl["response"]
    print(f"smoke/serverless_response_spike_p999,"
          f"{rp['spike_window']['p999_us']},"
          f"closed_loop_p99={rp['p99_us']}us")

    # elastic dkv: bootstrap >= 80% reduction, zero torn reads across a
    # live migration, worker-pull spike recovery
    from benchmarks.elastic_kv import check_gates as ek_gates
    from benchmarks.elastic_kv import run_suite as ek_suite

    ek = ek_suite(smoke=True)
    bad = ek_gates(ek)
    if bad:
        raise SystemExit("; ".join(bad))
    bs = ek["bootstrap"]
    print(f"smoke/elastic_kv_bootstrap,{bs['krcore_attach_mean_us']},"
          f"reduction={100 * bs['attach_reduction_vs_verbs']:.1f}%_vs_"
          f"verbs_{bs['verbs_attach_mean_us']}us")
    mig = ek["migration"]
    print(f"smoke/elastic_kv_migration_p99,{mig['p99_during_us']},"
          f"torn={mig['torn_reads']}_oracle_bad={mig['oracle_violations']}"
          f"_inflight={mig['reads_during_migration']}")
    sc = ek["autoscaler"]
    print(f"smoke/elastic_kv_autoscaler,{sc['krcore_wait_p99_us']},"
          f"wait_p99_reduction={100 * sc['wait_p99_reduction_vs_verbs']:.1f}"
          f"%_workers={sc['krcore_workers_peak']}")
    print("SMOKE_OK")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="substring filter on bench function names")
    ap.add_argument("--skip-roofline", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny batched-path smoke (CI without TPU)")
    args = ap.parse_args()
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()

    if args.smoke:
        _smoke()
        return

    from benchmarks.paper_figs import ALL_BENCHES

    benches = list(ALL_BENCHES)
    if not args.skip_roofline:
        from benchmarks.roofline import bench_roofline
        benches.append(bench_roofline)

    print("name,us_per_call,derived")
    failures = 0
    for bench in benches:
        if args.only and args.only not in bench.__name__:
            continue
        try:
            for name, us, derived in bench():
                print(f"{name},{us:.3f},{derived}")
        except Exception as e:                           # noqa: BLE001
            failures += 1
            print(f"{bench.__name__},nan,ERROR {type(e).__name__}: {e}")
            traceback.print_exc(file=sys.stderr)
        sys.stdout.flush()
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
