"""Device time per multi-get of the collectives that move data between
chips (all-gather, all-to-all, collective-permute and all-reduce, with
the start and done halves of their asynchronous forms), summed over the
window and averaged over the chips, ms. In a pool of memory nodes on
several chips (``MeshPoolRaceTable``) they bring every node's answers to
every chip before the gather into the keys' order. An operation is
known by its HLO opcode, the text of its ``XLA Ops`` event. None where
the trace holds none, as one chip gives."""

from __future__ import annotations

import re

from bench import trace as tr

COLLECTIVES = ("all-gather", "all-to-all", "collective-permute",
               "all-reduce")
_OPCODE = re.compile(r"[\]\})]\s+([a-z][\w-]*)\(")


def is_collective(hlo: str) -> bool:
    """An ``XLA Ops`` event whose opcode is one of ``COLLECTIVES``, or its
    ``-start`` or ``-done`` half."""
    m = _OPCODE.search(hlo.partition(" = ")[2])
    if m is None:
        return False
    return re.sub(r"-(start|done)$", "", m.group(1)) in COLLECTIVES


def read(run):
    w = None if run.trace is None else tr.window(run.trace)
    if w is None:
        return None
    lo, hi = w
    ns = sum(e - s for ops in run.trace.device_ops.values()
             for h, s, e in ops if lo <= s < hi and is_collective(h))
    if not ns:
        return None
    return ns / len(run.trace.device_ops) / len(tr.spans(run.trace, tr.SPAN)) \
        / 1e6
