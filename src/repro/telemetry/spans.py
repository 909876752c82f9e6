"""Named spans of the planner's and the engine's phases, written into the
JAX profiler's own trace.

``with span("mobility.nearest_ap"):`` opens a
``jax.profiler.TraceAnnotation`` named ``mcsa.mobility.nearest_ap``.  While
a profiler trace is being taken (``jax.profiler.trace(dir)``,
``jax.profiler.start_trace``, or a TensorBoard capture of a running
process) each span becomes a host event of the ``.xplane.pb``, on the same
clock as the device's operations, and its keyword arguments (plain ``int``
or ``float`` values already at hand) become the event's stats.  With no
trace running a span costs one annotation object, under a microsecond.
Nothing here starts a profiler, a server or a thread: whether a trace is
being collected decides everything.

``span(name, into=d, key=k)`` also adds the block's host-clock seconds to
``d[k]``; :attr:`repro.api.Session.timings` is fed that way, by the same
blocks that trace the session's phases.

:data:`SPANS` lists every name the program emits (without the ``mcsa.``
prefix).  The nesting: ``session.step`` > ``session.faults``,
``mobility.step``, ``planner.on_events`` (or ``session.on_handoffs``),
``session.serve``, ``session.feedback``; ``mobility.step`` > its three
phases; ``planner.on_events`` > its seven phases; ``engine.step`` >
``engine.admit`` > one ``engine.prefill``, ``engine.first_token`` and
``engine.slot_write`` per admission (each with the request's ``rid``),
then ``engine.decode``, ``engine.token_pull``, ``engine.emit``.
docs/ARCHITECTURE.md ("Spans") says what each one and each stat
measures, and which benchmark metric reads it.
"""
from __future__ import annotations

import time

from jax.profiler import TraceAnnotation

PREFIX = "mcsa."

SPANS = (
    # repro.api.Session
    "session.plan", "session.step", "session.faults", "session.on_handoffs",
    "session.serve", "session.feedback", "session.drain",
    # repro.core.mobility (the step itself is opened by the session)
    "mobility.step", "mobility.advance", "mobility.nearest_ap",
    "mobility.detect",
    # repro.core.planner.MCSAPlanner.on_events (opened by the session)
    "planner.on_events", "planner.fault_preamble", "planner.apply",
    "planner.flush", "planner.solve_prep", "planner.dispatch",
    "planner.admit", "planner.scatter",
    # repro.serving.engine.InferenceEngine
    "engine.step", "engine.admit", "engine.prefill", "engine.first_token",
    "engine.slot_write", "engine.decode", "engine.token_pull",
    "engine.emit", "engine.import_cache", "engine.cancel",
)


def span(name: str, *, into: dict | None = None, key: str | None = None,
         **stats):
    """Context manager: the trace event ``mcsa.<name>`` with ``stats`` as
    its arguments; with ``into``, the block's host-clock seconds are also
    added to ``into[key]``."""
    ann = TraceAnnotation(PREFIX + name, **stats)
    if into is None:
        return ann
    return _Timed(ann, into, key)


class _Timed:
    """A span that also adds its host-clock duration to ``into[key]``."""

    __slots__ = ("_ann", "_into", "_key", "_t0")

    def __init__(self, ann: TraceAnnotation, into: dict, key: str):
        self._ann = ann
        self._into = into
        self._key = key

    def __enter__(self):
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self._into[self._key] += time.perf_counter() - self._t0
        return self._ann.__exit__(*exc)
