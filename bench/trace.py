"""Reduce a JAX profiler trace to the benchmark's per-layer numbers.

A trace (``.xplane.pb``, read with ``jax.profiler.ProfileData``) holds one
plane per chip, ``/device:TPU:<n>``, whose ``XLA Ops`` line has one event
per operation that ran on that chip, named by its HLO text
(``%race_lookup.1 = (...) custom-call(...), custom_call_target=
"tpu_custom_call"``), and one host plane, ``/host:CPU``, whose lines hold
the host threads' events: the benchmark's own ``TraceAnnotation`` spans,
JAX's dispatch events and the runtime's transfer work. Host and device
events share one clock, in nanoseconds.

Everything here is plain interval arithmetic on ``(name, start_ns,
end_ns)`` triples, so the tests can drive it with synthetic events.
"""

from __future__ import annotations

import dataclasses
import re
from pathlib import Path

import numpy as np

#: the benchmark's own host span around each multi-get
SPAN = "multiget"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"


@dataclasses.dataclass
class Trace:
    #: per chip, the operations that ran on it: (HLO text, start, end) ns
    device_ops: dict[int, list[tuple[str, int, int]]]
    #: every host-thread event: (name, start, end) ns
    host: list[tuple[str, int, int]]


def load(path: str | Path) -> Trace:
    """Read one ``.xplane.pb`` file."""
    import jax
    return from_profile(jax.profiler.ProfileData.from_file(str(path)))


def from_profile(profile) -> Trace:
    device_ops: dict[int, list[tuple[str, int, int]]] = {}
    host: list[tuple[str, int, int]] = []
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops = device_ops.setdefault(int(m.group(1)), [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend(_events(line))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend(_events(line))
    return Trace(device_ops, host)


def _events(line):
    return [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
            for e in line.events]


# ------------------------------------------------------------ intervals
def union(intervals) -> np.ndarray:
    """Merge (start, end) pairs into disjoint sorted intervals, (k, 2)."""
    iv = np.asarray([(s, e) for s, e in intervals if e > s],
                    np.int64).reshape(-1, 2)
    if not len(iv):
        return iv
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    ends = np.maximum.accumulate(iv[:, 1])
    new = np.ones(len(iv), bool)
    new[1:] = iv[1:, 0] > ends[:-1]
    starts = iv[new, 0]
    last = np.r_[np.flatnonzero(new)[1:] - 1, len(iv) - 1]
    return np.stack([starts, ends[last]], axis=1)


def covered_ns(merged: np.ndarray, lo: int, hi: int) -> int:
    """Length of ``merged`` (disjoint, sorted) inside [lo, hi]."""
    if not len(merged) or hi <= lo:
        return 0
    s = np.clip(merged[:, 0], lo, hi)
    e = np.clip(merged[:, 1], lo, hi)
    return int(np.sum(e - s))


def gaps(merged: np.ndarray, lo: int, hi: int) -> np.ndarray:
    """The complement of ``merged`` inside [lo, hi], (k, 2)."""
    inside = merged[(merged[:, 1] > lo) & (merged[:, 0] < hi)] \
        if len(merged) else merged
    edges = np.concatenate(([lo], np.clip(inside.ravel(), lo, hi), [hi]))
    g = edges.reshape(-1, 2)
    return g[g[:, 1] > g[:, 0]]


# ------------------------------------------------------------- readings
def spans(trace: Trace, name: str) -> list[tuple[int, int]]:
    """The host spans called ``name``, in time order."""
    return sorted((s, e) for n, s, e in trace.host if n == name)


def window(trace: Trace) -> tuple[int, int] | None:
    """The traced window: from the first multi-get's start to the last
    one's end, or None where the trace holds none."""
    s = spans(trace, SPAN)
    return (s[0][0], s[-1][1]) if s else None


def busy(trace: Trace) -> dict[int, np.ndarray]:
    """Per chip, the union of its operations' intervals."""
    return {d: union((s, e) for _, s, e in ops)
            for d, ops in trace.device_ops.items()}


def busy_ns(trace: Trace, lo: int, hi: int) -> float:
    """Device-busy time inside [lo, hi], averaged over the chips."""
    per = [covered_ns(m, lo, hi) for m in busy(trace).values()]
    return float(np.mean(per)) if per else 0.0


def host_minus_device_ns(trace: Trace) -> list[float]:
    """For each multi-get span: its length less the device-busy time
    inside it (the host's share of that multi-get)."""
    merged = busy(trace)
    out = []
    for s, e in spans(trace, SPAN):
        dev = [covered_ns(m, s, e) for m in merged.values()]
        out.append((e - s) - (float(np.mean(dev)) if dev else 0.0))
    return out


def op_name(hlo: str) -> str:
    """The HLO instruction name of an ``XLA Ops`` event, without the ``%``
    and the ``.N`` suffix: ``race_lookup`` for ``%race_lookup.1 = ...``."""
    head = hlo.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def op_label(hlo: str) -> str:
    """A short label for an op: its instruction name and opcode."""
    head, _, rest = hlo.partition(" = ")
    m = re.search(r"[\]\})]\s+([a-z][\w-]*)\(", rest)
    return f"{head} {m.group(1)}" if m else hlo[:80]


def kernel_ops(trace: Trace, names, lo: int, hi: int):
    """The Pallas kernels (``tpu_custom_call``) whose instruction name is
    in ``names``, starting inside [lo, hi]: (hlo, start, end)."""
    names = set(names)
    return [(h, s, e) for ops in trace.device_ops.values()
            for h, s, e in ops
            if lo <= s < hi and 'custom_call_target="tpu_custom_call"' in h
            and op_name(h) in names]


def top_ops(trace: Trace, lo: int, hi: int, k: int = 10):
    """The ``k`` op labels with the most device time in [lo, hi], in
    seconds, averaged over the chips."""
    tot: dict[str, float] = {}
    n = max(len(trace.device_ops), 1)
    for ops in trace.device_ops.values():
        for h, s, e in ops:
            if lo <= s < hi:
                key = op_label(h)
                tot[key] = tot.get(key, 0.0) + (e - s) / 1e9 / n
    return sorted(([n_, t] for n_, t in tot.items()),
                  key=lambda x: -x[1])[:k]


def idle_gaps(trace: Trace, lo: int, hi: int, k: int = 10, top: int = 3):
    """The ``k`` longest stretches of [lo, hi] in which the first chip ran
    nothing, each labelled by what the host was doing in it: the ``top``
    host event names whose intervals cover most of the gap, each with the
    share it covers, and the share that no host event covers. The
    benchmark's own multi-get spans are left out."""
    merged = busy(trace)
    first = merged[min(merged)] if merged else np.zeros((0, 2), np.int64)
    g = gaps(first, lo, hi)
    g = g[np.argsort(g[:, 0] - g[:, 1], kind="stable")][:k]
    host = [(n, s, e) for n, s, e in trace.host
            if n != SPAN and e > s]
    hs = np.array([s for _, s, _ in host], np.int64)
    he = np.array([e for _, _, e in host], np.int64)
    out = []
    for a, b in g:
        a, b = int(a), int(b)
        idx = np.flatnonzero((hs < b) & (he > a))
        by_name: dict[str, list] = {}
        for i in idx:
            by_name.setdefault(host[i][0], []).append((hs[i], he[i]))
        cover = sorted(((covered_ns(union(iv), a, b), n)
                        for n, iv in by_name.items()), reverse=True)
        none = (b - a) - covered_ns(
            union((hs[i], he[i]) for i in idx), a, b)
        parts = [f"{n} {c / (b - a):.2f}" for c, n in cover[:top]]
        parts.append(f"no host event {none / (b - a):.2f}")
        out.append(["; ".join(parts), (b - a) / 1e9])
    return out
