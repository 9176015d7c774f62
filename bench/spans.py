"""The program's own spans inside each multi-get, on the profiler's clock.

The device lookup path opens host spans named ``race.<phase>``
(``repro.obs``); each lies inside the benchmark's ``multiget`` span
around the call. The readers here average, over the window's multi-gets,
the time such spans cover inside each, alone or less the time in which
the chip ran something. They return None where the trace holds no
multi-get or none of the spans asked for, as a program without these
spans gives.
"""

from __future__ import annotations

import numpy as np

from bench import trace as tr

#: the prefix of every span the program's lookup path opens
PREFIX = "race."


def _program(trace: tr.Trace, names) -> np.ndarray:
    """The union of the host spans whose name is in ``names`` (a set), or
    starts with ``PREFIX`` where ``names`` is None."""
    return tr.union((s, e) for n, s, e in trace.host
                    if (n.startswith(PREFIX) if names is None
                        else n in names))


def _per_multiget(trace: tr.Trace, names, part) -> float | None:
    """Mean over the window's multi-gets of ``part(span, device, busy,
    lo, hi)`` in ms: ``span`` is the union of the named spans, ``busy``
    per chip the union of its operations, ``device`` per chip the union
    of both."""
    multigets = tr.spans(trace, tr.SPAN)
    span = _program(trace, names)
    if not multigets or not len(span):
        return None
    busy = list(tr.busy(trace).values())
    device = [tr.union(np.concatenate([span, b])) for b in busy]
    return float(np.mean([part(span, device, busy, lo, hi)
                          for lo, hi in multigets])) / 1e6


def span_ms(trace: tr.Trace, names) -> float | None:
    """Time the spans ``names`` cover in a multi-get, ms."""
    return _per_multiget(
        trace, set(names),
        lambda span, device, busy, lo, hi: tr.covered_ns(span, lo, hi))


def span_less_device_ms(trace: tr.Trace, names) -> float | None:
    """Time the spans ``names`` cover in a multi-get while the chip runs
    nothing (averaged over chips), ms."""
    def part(span, device, busy, lo, hi):
        if not busy:
            return tr.covered_ns(span, lo, hi)
        return np.mean([tr.covered_ns(d, lo, hi) - tr.covered_ns(b, lo, hi)
                        for d, b in zip(device, busy)])
    return _per_multiget(trace, set(names), part)


def unspanned_ms(trace: tr.Trace) -> float | None:
    """Time of a multi-get in which neither a ``race.*`` span is open nor
    the chip runs anything (averaged over chips), ms."""
    def part(span, device, busy, lo, hi):
        if not busy:
            return (hi - lo) - tr.covered_ns(span, lo, hi)
        return np.mean([(hi - lo) - tr.covered_ns(d, lo, hi)
                        for d in device])
    return _per_multiget(trace, None, part)
