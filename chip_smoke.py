"""Chip smoke test: drive the device paths once, through the entry points a
user calls, at a deployment's size, and check each against its plain
reference. Everything runs in this one process, on one JAX client.

    python3 chip_smoke.py                # one TPU: chain, serve
    python3 chip_smoke.py --four-chips   # four TPUs: elastic scale events

Phases on one chip (the device RACE tables are checked against their
records in every run of the benchmark, ``bench/run.py``):

* chain — a 3-stage A->B->C serverless chain through ``ChainRunner``
  (k = 32 and 128, 1 KB payloads, 16 per slab), packed and unpacked by the
  stage kernel: outputs byte-equal to ``expected_outputs`` and at most
  ceil(k/16) doorbells per hop.
* serve — two ``ServingWorker``s of qwen2-0.5b at its published width
  sharing one ``ExecutablePool``: the second bootstrap is a pool hit.

With ``--four-chips`` only the elastic phase runs: ``ElasticTrainer`` at
qwen2-0.5b's width scales 1 -> 2 -> 4 through the pool, taking one step
at each size on the same batch from the same state.

Per-phase lines are JSON objects; the last line is ``{"ok": true,
"device": {...}}``. Any failed check raises, and the script exits
non-zero without that line. It also exits non-zero at once when JAX's
first device is not a TPU.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

#: max |loss(4 workers) - loss(1 worker)| / loss(1 worker): the same bf16
#: program on a quarter of the batch per device; only reduction order and
#: per-device tiling differ
LOSS_RTOL = 5e-3


def emit(**fields) -> None:
    print(json.dumps(fields), flush=True)


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# ------------------------------------------------------------------ chain
def phase_chain(seed: int) -> None:
    from repro.core import make_cluster
    from repro.kernels import interpret_mode
    from repro.serverless import (ChainRunner, ContainerPool,
                                  default_registry, expected_outputs)

    names = ("extract", "transform", "load")
    payload_bytes, slab = 1024, 16
    for k in (32, 128):
        cluster = make_cluster(n_nodes=3, n_meta=1)
        reg = default_registry(payload_bytes=payload_bytes)
        runner = ChainRunner(cluster, reg, ContainerPool(cluster, "krcore"),
                             "krcore", slab_payloads=slab)
        rng = np.random.RandomState(seed + k)
        payloads = [rng.randint(0, 256, payload_bytes).astype(np.uint8)
                    for _ in range(k)]

        def scenario():
            return (yield from runner.run_batch(
                names, ["n0", "n1", "n2"], k, payloads))

        t0 = time.perf_counter()
        rep = cluster.env.run_process(scenario(), f"chain.k{k}")
        wall_s = time.perf_counter() - t0
        exp = expected_outputs(reg, names, payloads)
        outs = rep.outputs or []
        mismatches = abs(len(outs) - len(exp)) + sum(
            not np.array_equal(a, b) for a, b in zip(outs, exp))
        doorbells = max(h.doorbells for h in rep.hops)
        budget = math.ceil(k / slab)
        emit(phase="chain", k=k, payload_bytes=payload_bytes,
             slab_payloads=slab, stage_kernel="interpret"
             if interpret_mode() else "compiled", mismatches=mismatches,
             doorbells_per_hop=doorbells, doorbell_budget=budget,
             wall_s=round(wall_s, 3))
        check(mismatches == 0, f"chain k={k}: {mismatches} outputs differ")
        check(doorbells <= budget,
              f"chain k={k}: {doorbells} doorbells per hop > {budget}")


# ------------------------------------------------------------------ serve
def phase_serve(seed: int) -> None:
    import jax
    from repro.configs import get_config
    from repro.elastic import ExecutablePool
    from repro.launch.serve import ServingWorker
    from repro.models import init_params

    cfg = get_config("qwen2_0_5b")
    slots, max_len, steps = 4, 128, 8
    emit(phase="serve", reduced=[
        f"{slots} slots x {steps} decoded tokens, max_len {max_len}"])
    params = init_params(cfg, jax.random.PRNGKey(seed))
    pool = ExecutablePool()
    toks = []
    for i in range(2):
        w = ServingWorker(cfg, params, slots, max_len, pool=pool)
        t = w.decode_tokens(np.zeros(slots, np.int32), steps)
        toks.append(t)
        emit(phase="serve", worker=i, arch=cfg.name, layers=cfg.n_layers,
             d_model=cfg.d_model, vocab=cfg.vocab, slots=slots,
             max_len=max_len, tokens_decoded=int(t.size),
             bootstrap_s=round(w.bootstrap_s, 4),
             pool_hits=pool.stat_hits, pool_misses=pool.stat_misses)
    check(pool.stat_hits == 1 and pool.stat_misses == 1,
          "the second worker's bootstrap was not a pool hit")
    for t in toks:
        check(t.shape == (slots, steps) and t.min() >= 0
              and t.max() < cfg.vocab, f"decoded tokens out of range: {t}")
    check(np.array_equal(toks[0], toks[1]),
          "two workers on the same params decoded different tokens")


# ----------------------------------------------------------- four chips
def phase_elastic(seed: int) -> None:
    import jax
    from repro.configs import get_config
    from repro.elastic import ElasticTrainer
    from repro.launch.steps import make_train_step
    from repro.models import init_params
    from repro.optim import adamw_init

    layers, batch_size, seq = 4, 8, 256
    cfg = dataclasses.replace(get_config("qwen2_0_5b"), n_layers=layers)
    emit(phase="elastic", reduced=[
        f"n_layers 24 -> {layers}",
        f"global batch {batch_size} x seq {seq}"])

    def make_step(mesh):
        inner = make_train_step(cfg, lr=1e-4)

        def step(state, batch):
            params, opt = state
            loss, params, opt = inner(params, opt, batch)
            return loss, (params, opt)
        return step

    def init_state():
        p = init_params(cfg, jax.random.PRNGKey(seed))
        return (p, adamw_init(p))

    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, cfg.vocab, (batch_size, seq + 1), np.int32)
    batch = {"tokens": tokens[:, :-1], "labels": tokens[:, 1:]}
    tr = ElasticTrainer(cfg, make_step, init_state, ladder=(1, 2, 4),
                        example_batch=batch)
    t0 = time.perf_counter()
    tr.prewarm()
    emit(phase="elastic", prewarm_s=round(time.perf_counter() - t0, 2),
         ladder=[1, 2, 4])
    losses, state0 = {}, None
    for n in (1, 2, 4):
        tr.state = state0
        ev = tr.scale_to(n)
        state0 = state0 if state0 is not None else tr.state
        placed = tr.place_batch(batch)
        losses[n] = float(tr.train_step(placed))
        shards = sorted((s.device.id, s.data.shape)
                        for s in placed["tokens"].addressable_shards)
        emit(phase="elastic", workers=n, event=ev["kind"],
             control_s=round(ev["control_s"], 4), loss=losses[n],
             batch_shards=[[d, list(shape)] for d, shape in shards])
        check(ev["kind"] == "generic",
              f"scale to {n} was a {ev['kind']} event, not a pool hit")
        check(len({d for d, _ in shards}) == n,
              f"the batch landed on {len(set(shards))} devices, not {n}")
    rel = abs(losses[4] - losses[1]) / abs(losses[1])
    emit(phase="elastic", loss_1=losses[1], loss_4=losses[4],
         rel_diff=rel, rtol=LOSS_RTOL)
    check(math.isfinite(losses[1]) and rel <= LOSS_RTOL,
          f"4-way loss {losses[4]} vs 1-way {losses[1]}: rel {rel}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip elastic phase")
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU: JAX's first device is "
              f"{dev.platform!r} ({dev.device_kind})", file=sys.stderr)
        return 2
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: {need} chips needed, JAX sees {len(devices)}",
              file=sys.stderr)
        return 2

    from repro.launch.cache import enable_compile_cache
    emit(phase="setup", compile_cache=enable_compile_cache(),
         device_kind=dev.device_kind, devices=len(devices))
    phases = ([phase_elastic] if args.four_chips
              else [phase_chain, phase_serve])
    for phase in phases:
        t0 = time.perf_counter()
        phase(args.seed)
        emit(phase=phase.__name__[len("phase_"):], done=True,
             seconds=round(time.perf_counter() - t0, 2))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
