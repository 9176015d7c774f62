import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

MUST be run as its own process (the XLA_FLAGS line above has to execute
before jax initializes devices — that is why it precedes every import).

    PYTHONPATH=src python -m repro.launch.dryrun --arch gemma2-2b \
        --shape train_4k --mesh single --out results/gemma2.json
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both

Per cell it records memory_analysis(), cost_analysis(), and the collective
traffic parsed from the optimized HLO (launch/hlo_stats.py) — the inputs to
the roofline analysis (EXPERIMENTS.md §Roofline).
"""

import argparse                                              # noqa: E402
import json                                                  # noqa: E402
import time                                                  # noqa: E402
import traceback                                             # noqa: E402

import jax                                                   # noqa: E402
from jax.sharding import PartitionSpec as P                  # noqa: E402

from repro.configs import all_archs, get_config, skip_shapes  # noqa: E402
from repro.distributed import (batch_specs, cache_specs,      # noqa: E402
                               param_specs)
from repro.distributed.shardings import opt_state_specs      # noqa: E402
from repro.launch.hlo_stats import collective_stats          # noqa: E402
from repro.launch.mesh import make_production_mesh           # noqa: E402
from repro.launch.steps import (input_specs, make_decode_step,  # noqa: E402
                                make_prefill_step, make_train_step)
from repro.models.config import SHAPES_BY_NAME               # noqa: E402


def depth_variants(cfg):
    """Two shallow UNROLLED variants (a, b) and the multiplier such that
    exact_cost = F_a + mult * (F_b - F_a).

    XLA cost_analysis counts a while-loop body once, so the scanned
    full-depth lowering under-reports per-layer cost. Layers are identical
    within a segment, so cost is affine in depth — two unrolled points
    recover it exactly (see models/model.py::seg_scan).
    """
    import dataclasses
    r = dataclasses.replace
    if cfg.family == "hybrid":
        per = cfg.attn_every
        tail = cfg.n_layers % per
        a, b = per + tail, 2 * per + tail
        mult = (cfg.n_layers - a) / per
        return (r(cfg, n_layers=a, scan_layers=False),
                r(cfg, n_layers=b, scan_layers=False), mult)
    if cfg.family == "encdec":
        return (r(cfg, enc_layers=1, dec_layers=1, n_layers=2,
                  scan_layers=False),
                r(cfg, enc_layers=2, dec_layers=2, n_layers=4,
                  scan_layers=False),
                cfg.enc_layers - 1)
    if cfg.layer_pattern == "local_global":
        return (r(cfg, n_layers=2, scan_layers=False),
                r(cfg, n_layers=4, scan_layers=False),
                (cfg.n_layers - 2) / 2)
    if cfg.mla and cfg.first_k_dense:
        a = cfg.first_k_dense + 1
        return (r(cfg, n_layers=a, scan_layers=False),
                r(cfg, n_layers=a + 1, scan_layers=False),
                cfg.n_layers - a)
    return (r(cfg, n_layers=1, scan_layers=False),
            r(cfg, n_layers=2, scan_layers=False),
            cfg.n_layers - 1)


def _analyze(cfg, shape_name, multi_pod):
    """Lower + compile one configuration; returns (compiled, timings)."""
    shape = SHAPES_BY_NAME[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    spec = input_specs(cfg, shape_name)
    kind, args = spec["kind"], spec["args"]

    with jax.set_mesh(mesh):
        pspecs = param_specs(cfg, args[0], mesh)
        if kind == "train":
            fn = make_train_step(cfg)
            in_sh = (pspecs, opt_state_specs(cfg, args[1], pspecs),
                     batch_specs(cfg, mesh, "train"))
            out_sh = (P(), pspecs, in_sh[1])
        elif kind == "prefill":
            fn = make_prefill_step(cfg, max_len=shape.seq_len)
            csh = jax.eval_shape(fn, *args)
            in_sh = (pspecs, batch_specs(cfg, mesh, "prefill"))
            out_sh = (P(), cache_specs(cfg, mesh, csh[1],
                                       shape.global_batch))
        else:
            fn = make_decode_step(cfg)
            cspec = cache_specs(cfg, mesh, args[1], shape.global_batch)
            from repro.distributed.shardings import _dp_or_none
            dp = _dp_or_none(mesh, shape.global_batch)
            in_sh = (pspecs, cspec, P(dp), P())
            out_sh = (P(dp, None), cspec)

        # buffer donation: decode steps donate the KV/state cache (in-place
        # update instead of a full copy per token — §Perf iteration C3);
        # train steps donate params + optimizer state (standard practice).
        donate = ()
        if getattr(cfg, "donate", False):
            donate = {"train": (0, 1), "prefill": (), "decode": (1,)}[kind]
        t0 = time.time()
        jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                         donate_argnums=donate)
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    colls = collective_stats(compiled.as_text())
    return {
        "kind": kind,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
        },
        "flops_per_device": cost.get("flops", 0.0),
        "bytes_per_device": cost.get("bytes accessed", 0.0),
        "collectives": {
            "counts": colls.counts,
            "result_bytes": colls.result_bytes,
            "link_bytes_per_device": colls.link_bytes,
        },
    }


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               overrides: dict = None, exact: bool = True):
    """One cell: full-depth scanned compile (compilability + memory proof)
    plus, on the single-pod mesh, two shallow unrolled compiles that
    extrapolate exact per-device FLOPs/bytes/collective traffic."""
    cfg = get_config(arch)
    if overrides:
        import dataclasses
        cfg = dataclasses.replace(cfg, **overrides)
    out = {"arch": arch, "shape": shape_name,
           "mesh": "2x16x16" if multi_pod else "16x16", "status": "ok"}
    out.update(_analyze(cfg, shape_name, multi_pod))

    if exact and not multi_pod:
        cfg_a, cfg_b, mult = depth_variants(cfg)
        ra = _analyze(cfg_a, shape_name, multi_pod)
        rb = _analyze(cfg_b, shape_name, multi_pod)

        def extrap(fa, fb):
            return fa + mult * (fb - fa)

        ca, cb = ra["collectives"], rb["collectives"]
        out["exact"] = {
            "flops_per_device": extrap(ra["flops_per_device"],
                                       rb["flops_per_device"]),
            "bytes_per_device": extrap(ra["bytes_per_device"],
                                       rb["bytes_per_device"]),
            "link_bytes_per_device": extrap(
                ca["link_bytes_per_device"], cb["link_bytes_per_device"]),
            "coll_counts": {
                k: extrap(ca["counts"][k], cb["counts"][k])
                for k in ca["counts"]},
            "depth_points": [cfg_a.n_layers, cfg_b.n_layers],
            "mult": mult,
        }
    return out


def run_cell(arch, shape_name, multi_pod, overrides=None, exact=True):
    try:
        return lower_cell(arch, shape_name, multi_pod, overrides,
                          exact=exact)
    except Exception as e:                                   # noqa: BLE001
        return {"arch": arch, "shape": shape_name,
                "mesh": "2x16x16" if multi_pod else "16x16",
                "status": "error", "error": f"{type(e).__name__}: {e}",
                "trace": traceback.format_exc()[-2000:]}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--override", default=None,
                    help="JSON dict of ModelConfig overrides (perf knobs)")
    ap.add_argument("--no-exact", action="store_true",
                    help="skip the exact-cost depth-variant lowerings")
    args = ap.parse_args()

    overrides = json.loads(args.override) if args.override else None
    archs = all_archs() if args.all or not args.arch else [args.arch]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    results = []
    for arch in archs:
        skips = skip_shapes(arch)
        shapes = ([args.shape] if args.shape
                  else list(SHAPES_BY_NAME.keys()))
        for shape_name in shapes:
            if shape_name in skips:
                results.append({"arch": arch, "shape": shape_name,
                                "status": "skip",
                                "reason": skips[shape_name]})
                print(f"SKIP {arch} {shape_name}: {skips[shape_name]}")
                continue
            for mp in meshes:
                r = run_cell(arch, shape_name, mp, overrides,
                             exact=not args.no_exact)
                results.append(r)
                tag = "OK  " if r["status"] == "ok" else "FAIL"
                extra = (f"compile={r.get('compile_s')}s "
                         f"flops/dev={r.get('flops_per_device', 0):.3g}"
                         if r["status"] == "ok"
                         else r.get("error", ""))
                print(f"{tag} {arch} {shape_name} "
                      f"{'512' if mp else '256'}chips {extra}", flush=True)

    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
        print(f"wrote {args.out}")
    n_fail = sum(1 for r in results if r["status"] == "error")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
