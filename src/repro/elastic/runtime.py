"""Elastic runtime: the paper's control plane mapped onto TPU jobs.

KRCORE's structure transfers one-to-one (DESIGN.md §2b):

  hybrid QP pool          -> ``ExecutablePool``: generic ladder-compiled
                             executables (DC analogue: usable for ANY
                             worker count in the ladder, O(1) state) +
                             specialized per-exact-config executables
                             (RC analogue) compiled in the BACKGROUND and
                             hot-swapped at a step boundary (the transfer
                             protocol's FIFO flush = finish current step,
                             swap, continue).
  meta server             -> tiny replicated job metadata (mesh shape,
                             checkpoint step, data offset) in a KV table;
                             device-side lookups via kvs.DeviceRaceTable.
  worker bootstrap        -> attach to pre-initialized pool state instead
                             of cold mesh formation + compile.

Also here: straggler mitigation (speculative re-dispatch) and the elastic
trainer used by examples/elastic_train.py and the integration tests.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# =========================================================== executable pool
@dataclasses.dataclass
class PoolEntry:
    value: Any
    kind: str                  # "generic" | "specialized"
    uses: int = 0


class ExecutablePool:
    """Compiled-executable cache with background specialization.

    ``get(key)`` never blocks on compilation: it returns a generic entry
    (coarsened key) when the exact one is missing, and (optionally) kicks
    off a background specialize — exactly the DCQP-now / RCQP-later policy
    of the paper's hybrid pool.
    """

    def __init__(self, coarsen: Callable[[Any], Any] = lambda k: None,
                 max_entries: int = 64):
        self._entries: Dict[Any, PoolEntry] = {}
        self._lock = threading.Lock()
        self._inflight: Dict[Any, threading.Thread] = {}
        self._coarsen = coarsen
        self.max_entries = max_entries
        self.stat_hits = 0
        self.stat_misses = 0

    def put(self, key, value, kind="specialized"):
        with self._lock:
            if len(self._entries) >= self.max_entries:
                lru = min(self._entries.items(), key=lambda kv: kv[1].uses)
                del self._entries[lru[0]]
            self._entries[key] = PoolEntry(value, kind)

    def get(self, key) -> Tuple[str, Optional[Any]]:
        with self._lock:
            ent = self._entries.get(key)
            if ent is not None:
                ent.uses += 1
                self.stat_hits += 1
                return ent.kind, ent.value
            coarse = self._coarsen(key)
            ent = self._entries.get(coarse)
            if ent is not None:
                ent.uses += 1
                return "generic", ent.value
            self.stat_misses += 1
            return "miss", None

    def specialize_async(self, key, builder: Callable[[], Any]) -> None:
        """Background compile (never on the caller's critical path)."""
        with self._lock:
            if key in self._entries or key in self._inflight:
                return

        def work():
            self.put(key, builder(), "specialized")
            with self._lock:
                self._inflight.pop(key, None)

        t = threading.Thread(target=work, daemon=True)
        with self._lock:
            self._inflight[key] = t
        t.start()

    def wait_all(self) -> None:
        for t in list(self._inflight.values()):
            t.join()


# ===================================================== straggler mitigation
@dataclasses.dataclass
class StragglerPolicy:
    """Detect laggards from per-worker step durations."""
    threshold: float = 2.0         # x median
    min_samples: int = 3

    def detect(self, durations: Sequence[float]) -> List[int]:
        if len(durations) < self.min_samples:
            return []
        med = float(np.median(durations))
        if med <= 0:
            return []
        return [i for i, d in enumerate(durations)
                if d > self.threshold * med]


def speculative_map(task_fn: Callable[[int, int], Any], n_tasks: int,
                    worker_speeds: Sequence[float],
                    policy: Optional[StragglerPolicy] = None
                    ) -> Tuple[List[Any], float, Dict]:
    """Deterministic simulation of speculative re-execution.

    Tasks are dealt to workers with the given speed factors (duration =
    speed). When a worker's expected finish exceeds policy.threshold x the
    median, its task is re-dispatched to the earliest-free fast worker;
    first copy to finish wins (the standard backup-task trick).
    Returns (results, makespan, stats).
    """
    policy = policy or StragglerPolicy()
    free_at = [0.0] * len(worker_speeds)
    finish: List[Optional[float]] = [None] * n_tasks
    results: List[Any] = [None] * n_tasks
    assigned: List[Tuple[int, int, float]] = []      # (task, worker, done)
    backups = 0
    for t in range(n_tasks):
        w = min(range(len(free_at)), key=lambda i: free_at[i])
        start = free_at[w]
        done = start + worker_speeds[w]
        free_at[w] = done
        assigned.append((t, w, done))
        results[t] = task_fn(t, w)
        finish[t] = done
    durations = [worker_speeds[w] for (_, w, _) in assigned]
    for idx in policy.detect(durations):
        t, w, done = assigned[idx]
        # re-dispatch to the fastest currently-free worker
        cand = min(range(len(free_at)), key=lambda i: free_at[i]
                   + worker_speeds[i])
        alt_done = free_at[cand] + worker_speeds[cand]
        if alt_done < done:
            free_at[cand] = alt_done
            finish[t] = alt_done
            results[t] = task_fn(t, cand)
            backups += 1
    makespan = max(finish)
    return results, makespan, {"backups": backups}


# ============================================================ elastic trainer
class ElasticTrainer:
    """Data-parallel trainer whose worker count can change between steps.

    Scale events go through the KRCORE-style control plane: executable
    lookup in the pool (generic hit = microsecond-scale bootstrap;
    miss = compile, charged to the event and recorded), then state
    redistribution via device_put to the new mesh.
    """

    def __init__(self, cfg, make_step: Callable[[Any], Any],
                 init_state: Callable[[], Any], ladder: Sequence[int] = (),
                 example_batch: Optional[Dict[str, np.ndarray]] = None):
        self.cfg = cfg
        self.make_step = make_step
        self.devices = jax.devices()
        self.pool = ExecutablePool(coarsen=self._coarsen)
        self.events: List[Dict] = []
        self.n_workers = 0
        self.state = None
        self._step_fn = None
        self._mesh = None
        self._ladder = tuple(ladder)
        self._init_state = init_state
        self._example_batch = example_batch

    # -- control plane -----------------------------------------------------
    @staticmethod
    def _coarsen(key):
        """Generic key: ladder executables serve any count of that size."""
        return ("ladder", key[1])

    def _mesh_for(self, n: int) -> Mesh:
        devs = np.array(self.devices[:n]).reshape(n, 1)
        return Mesh(devs, ("data", "model"))

    def _builder(self, n: int):
        def build():
            mesh = self._mesh_for(n)
            with jax.set_mesh(mesh):
                step = self.make_step(mesh)
                if self._example_batch is None:
                    return (mesh, jax.jit(step))
                # AOT-compile with explicit shardings so a later pool hit
                # really skips XLA (jax.jit alone is lazy)
                state_struct = jax.eval_shape(self._init_state)
                batch_struct = {
                    k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                    for k, v in self._example_batch.items()}
                state_sh = jax.tree_util.tree_map(lambda _: P(),
                                                  state_struct)
                batch_sh = {k: P("data", *([None] * (v.ndim - 1)))
                            for k, v in self._example_batch.items()}
                out_sh = (P(), state_sh)
                fn = jax.jit(step, in_shardings=(state_sh, batch_sh),
                             out_shardings=out_sh)
                compiled = fn.lower(state_struct, batch_struct).compile()
            return (mesh, compiled)
        return build

    def prewarm(self) -> None:
        """Boot-time ladder compile (the statically-initialized DCQPs)."""
        for n in self._ladder:
            self.pool.put(("ladder", n), self._builder(n)(), kind="generic")

    def scale_to(self, n: int) -> Dict:
        """Elastic resize; returns the timing event (the paper's metric)."""
        t0 = time.time()
        key = ("exact", n)
        kind, entry = self.pool.get(key)
        if entry is None:
            # miss: compile now (the Verbs-analogue cold path) — measured
            entry = self._builder(n)()
            self.pool.put(key, entry)
            kind = "cold"
        mesh, fn = entry
        # state redistribution (weights resharded onto the new mesh)
        if self.state is not None:
            spec = jax.tree_util.tree_map(lambda _: P(), self.state)
            self.state = jax.device_put(
                self.state, jax.tree_util.tree_map(
                    lambda s: NamedSharding(mesh, s), spec))
        else:
            with jax.set_mesh(mesh):
                self.state = self._init_state()
        self._mesh, self._step_fn = mesh, fn
        old_n, self.n_workers = self.n_workers, n
        ev = {"kind": kind, "from": old_n, "to": n,
              "control_s": time.time() - t0}
        self.events.append(ev)
        return ev

    # -- data plane ---------------------------------------------------------
    def place_batch(self, batch) -> Dict[str, Any]:
        """Shard each batch array's leading axis over the current mesh's
        data axis (one slice per worker)."""
        return {k: jax.device_put(v, NamedSharding(
            self._mesh, P("data", *([None] * (v.ndim - 1)))))
            for k, v in batch.items()}

    def train_step(self, batch) -> Any:
        loss, self.state = self._step_fn(self.state, self.place_batch(batch))
        return loss
