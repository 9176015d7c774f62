"""Spans and counters for the program's own phases, on the profiler's clock.

A span is a ``jax.profiler.TraceAnnotation``. While a profiler session
runs it lands on the host plane of the same trace as the device's
``XLA Ops``, on the same nanosecond clock, so each phase of a request
shows beside the device work it waits for. With no session running a
span costs about a microsecond; nothing switches spans off.

A request groups the spans of one call and names the stats they count
into::

    with obs.request(table.stats):          # once per lookup_batch
        with obs.span("race.prep", keys=len(keys)):
            ...
        with obs.span("race.group") as add:
            ...
            add(slots=n_slots)              # known once the phase ran

Every span inside a request carries ``call``, a number unique in the
process to that request, so the spans of one multi-get read back
together. A span's other keyword arguments become arguments of the trace
event; those that are integers and name a field of the request's stats
are also added to it, so the same counts are readable without a trace.
Spans are leaves around single phases: the caller's own span, if any,
is their parent.

The device RACE tables (``repro.kvs.race``) open one request per
``lookup_batch`` and count into their ``stats`` (``LookupStats``:
``calls``, ``keys``, ``h2d_bytes``, ``table_ships``, ``slots``,
``padded_slots``, ``h2d_buffers`` (pool tables), and ``blocks``, which
the pool tables sum on the device with no span). Their spans:

=================  ====================================================
``race.prep``      host hashing (and shard or node routing); ``keys``
``race.stack``     sharded: the resident stacked tables handed to the
                   kernel path (no copy)
``race.group``     sharded, pool mesh: queries grouped and padded per
                   shard or node; ``slots``, ``padded_slots``, ``qcap``
``race.to_device`` host-to-device copies, until they are on the device;
                   ``h2d_bytes``. The whole table ships in a span of
                   its own, with ``table_ships``, only on a lookup that
                   follows an insert (or the first). The pool tables
                   pack a lookup's queries into one array, put once
                   (sharded by node over a mesh), and count the device
                   buffers each put creates in ``h2d_buffers``: one a
                   device for the queries, one a host array for a ship
``race.kernel``    dispatch of the jitted lookup; ``variant`` is
                   ``scalar``, ``sharded``, ``pool``, ``pool_mesh`` (or
                   ``ref``). The sharded one also dispatches the gather
                   that puts the padded answers in the keys' order on
                   the device; ``pool_mesh`` is one SPMD program that
                   runs the pool kernel on every node and gathers the
                   answers into the keys' order across the devices
=================  ====================================================

To see them, trace a stretch of calls and open the trace in a viewer::

    jax.profiler.start_trace("/tmp/trace", create_perfetto_trace=True)
    ...                                     # lookups
    jax.profiler.stop_trace()

``/tmp/trace/plugins/profile/<time>/`` then holds ``*.xplane.pb``, for
TensorBoard's profile plugin or ``jax.profiler.ProfileData.from_file``,
and ``perfetto_trace.json.gz``, for https://ui.perfetto.dev. A span's
arguments show as the event's arguments there.
"""

from __future__ import annotations

import contextlib
import contextvars
import itertools

import jax

_current: contextvars.ContextVar = contextvars.ContextVar(
    "repro_obs_request", default=None)
_call_ids = itertools.count(1)


@contextlib.contextmanager
def request(stats):
    """Open one request that counts into ``stats`` (a dataclass of
    integer totals whose ``calls`` is bumped here). Requests nest: the
    inner one has its own call id and stats until it exits."""
    stats.calls += 1
    token = _current.set((next(_call_ids), stats))
    try:
        yield
    finally:
        _current.reset(token)


@contextlib.contextmanager
def span(name: str, **args):
    """A trace span ``name`` with ``args`` (numbers or strings), tagged
    with the current request's ``call``. Integer ``args`` that name a
    field of the request's stats are added to it. Yields ``add(**args)``
    for arguments known only once the phase has run, counted alike."""
    req = _current.get()
    stats = None
    if req is not None:
        call, stats = req
        args = {"call": call, **args}
    with jax.profiler.TraceAnnotation(name, **args) as annotation:
        def add(**more):
            _count(stats, more)
            annotation.set_metadata(**more)

        _count(stats, args)
        yield add


def _count(stats, args: dict) -> None:
    if stats is None:
        return
    for k, v in args.items():
        if isinstance(v, int) and hasattr(stats, k):
            setattr(stats, k, getattr(stats, k) + v)
