"""The control of the correctness check: it has to come out incorrect.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds <n>...

The plain reference is put in the program's place and computed one
precision below what the configuration states: the records are held on
the chip in bfloat16 instead of float32, and each multi-get gathers them
there. Everything else is the cell's own run: its records, its traffic,
its window and its check. For each seed the script prints one JSON line
with the numbers the check compared; every line should read ``correct:
false``. The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import run as harness  # noqa: E402


class Bfloat16Reference:
    """The system adapter of the control: ``kv_index`` with its records
    rounded to bfloat16 on the device."""

    def build(self, config, keys, values):
        import jax.numpy as jnp
        self.reference = harness.load_module(
            harness.BENCH / "references" / f"{config['reference']}.py"
        ).build(keys, values)
        self.records = jnp.asarray(values, jnp.bfloat16)
        return self

    def multiget(self, table, keys):
        import jax.numpy as jnp
        rows = np.array([self.reference.row.get(k, -1)
                         for k in keys.tolist()], np.int64)
        found = rows >= 0
        v = jnp.take(self.records, jnp.asarray(np.maximum(rows, 0)), axis=0)
        v = jnp.where(jnp.asarray(found)[:, None], v.astype(jnp.float32), 0)
        return np.asarray(v), found.astype(np.int32)

    def warm_up(self, table, batches):
        self.multiget(table, batches[0])
        return [len(batches[0])]


def run(cell, seeds, seconds):
    cell.system = Bfloat16Reference()
    for seed in seeds:
        out = harness.run_cell(cell, seed, seconds, False,
                               t_start=time.perf_counter())
        yield {"workload": cell.name, "seed": seed,
               "correct": out["correct"],
               "answers_checked": out["answers_checked"],
               **{k: c["value"] for k, c in out["checks"].items()}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = harness.resolve(harness.load_spec(), args.workload)
    for line in run(cell, args.seeds, args.seconds):
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
