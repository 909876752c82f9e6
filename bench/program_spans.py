"""The program's own spans (``mcsa.*``, ``repro.telemetry.spans``) in a run's
trace: self time, stats, and device idle time inside each span.

A ``--trace 1`` run leaves its ``.xplane.pb`` under the harness's trace
directory while the per-layer readers run; ``trace_path`` finds it (the
path in ``facts["trace_path"]`` where one is given, else the newest trace
under ``.bench_trace``), and ``load`` parses it once per path.  The file
is read with ``jax.profiler.ProfileData`` and nothing else.

Spans are the host events whose name starts with ``mcsa.``; they nest by
time on the thread that opened them.  Device busy time is the union of
the ``XLA Ops`` events of the device planes, as in ``bench/trace.py``; on
the CPU backend, whose trace has no device plane, the XLA CPU runtime's
program executions (``ThunkExecutor::Execute``) stand in for it.  Every
interval is clipped to the harness's ``bench.window`` span.  The
arithmetic is in pure functions over ``(start, end)`` intervals, in ns.

A reader returns None where the run was not traced, or where the program
wrote no such span (a program that predates them).
"""
from __future__ import annotations

import bisect
import functools
import glob
import os
import re
import statistics
from typing import Dict, List, Optional, Sequence, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE_ROOT = os.path.join(ROOT, ".bench_trace")
PREFIX = "mcsa."
WINDOW_SPAN = "bench.window"
_DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
_CPU_EXECUTE = "ThunkExecutor::Execute"


# -- interval arithmetic -------------------------------------------------
def union(intervals: Sequence[Tuple[float, float]]) -> List[List[float]]:
    """Merged ``[start, end]`` intervals, sorted."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


class Busy:
    """Merged busy intervals, indexed so that the busy length inside any
    ``[a, b]`` takes two binary searches."""

    def __init__(self, intervals: Sequence[Tuple[float, float]]):
        merged = union(intervals)
        self.starts = [s for s, _ in merged]
        self.ends = [e for _, e in merged]
        self.before = [0.0]             # busy length before interval i
        for s, e in merged:
            self.before.append(self.before[-1] + e - s)

    def __bool__(self) -> bool:
        return bool(self.starts)

    def _upto(self, t: float) -> float:
        i = bisect.bisect_right(self.starts, t) - 1
        if i < 0:
            return 0.0
        return self.before[i] + min(self.ends[i], t) - self.starts[i]

    def covered(self, a: float, b: float) -> float:
        """Length of ``[a, b]`` the intervals cover."""
        return self._upto(b) - self._upto(a) if b > a else 0.0


def nest(spans: Sequence[tuple]) -> List[Optional[int]]:
    """Index of each span's innermost enclosing span on the same thread
    (None at the top).  ``spans`` are ``(name, start, end, thread, ...)``
    tuples; a span encloses another that starts and ends inside it."""
    parents: List[Optional[int]] = [None] * len(spans)
    order = sorted(range(len(spans)),
                   key=lambda i: (spans[i][3], spans[i][1], -spans[i][2]))
    stack: List[int] = []
    thread = None
    for i in order:
        _, s, e, th = spans[i][:4]
        if th != thread:
            stack, thread = [], th
        while stack and spans[stack[-1]][2] < e:
            stack.pop()
        parents[i] = stack[-1] if stack else None
        stack.append(i)
    return parents


def self_times(spans: Sequence[tuple], parents: Sequence[Optional[int]],
               measure=None) -> List[float]:
    """Each span's ``measure`` (its length by default) less that of its
    children: the time it spent in no span of its own."""
    measure = measure or (lambda sp: sp[2] - sp[1])
    own = [measure(sp) for sp in spans]
    out = list(own)
    for i, p in enumerate(parents):
        if p is not None:
            out[p] -= own[i]
    return out


def idle_inside(busy: Busy, a: float, b: float) -> float:
    """Time in ``[a, b]`` at which the device ran nothing."""
    return max(0.0, (b - a) - busy.covered(a, b))


def clip(spans: Sequence[tuple], lo: float, hi: float) -> List[tuple]:
    """The spans that overlap ``[lo, hi]``, cut to it."""
    return [(sp[0], max(sp[1], lo), min(sp[2], hi)) + tuple(sp[3:])
            for sp in spans if sp[2] > lo and sp[1] < hi]


# -- the trace -----------------------------------------------------------
def trace_path(facts: dict) -> Optional[str]:
    """The run's ``.xplane.pb``, or None on an untraced run."""
    if "trace" not in facts:
        return None
    if facts.get("trace_path"):
        return facts["trace_path"]
    files = glob.glob(os.path.join(TRACE_ROOT, "**", "*.xplane.pb"),
                      recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def _parse(path: str) -> dict:
    from jax.profiler import ProfileData
    spans, window, device, cpu = [], [], [], []
    for plane in ProfileData.from_file(path).planes:
        lines = list(plane.lines)
        if _DEVICE_PLANE.match(plane.name):
            ops = [ln for ln in lines if ln.name == "XLA Ops"] or lines
            device.extend((e.start_ns, e.start_ns + e.duration_ns)
                          for ln in ops for e in ln.events
                          if e.duration_ns > 0)
            continue
        if not plane.name.startswith("/host:"):
            continue
        for li, ln in enumerate(lines):
            for e in ln.events:
                name = e.name
                if name.startswith(PREFIX):
                    spans.append((name[len(PREFIX):], e.start_ns,
                                  e.start_ns + e.duration_ns,
                                  (plane.name, li), dict(e.stats)))
                elif name == WINDOW_SPAN:
                    window.append((e.start_ns, e.start_ns + e.duration_ns))
                elif name.startswith(_CPU_EXECUTE) and e.duration_ns > 0:
                    cpu.append((e.start_ns, e.start_ns + e.duration_ns))
    return {"spans": spans, "window": window, "busy": device or cpu}


def load(path: str) -> dict:
    """The trace's program spans clipped to the window, with their
    parents, and the device's busy intervals: ``{"spans", "parents",
    "busy", "window"}`` (``window`` as ``(lo, hi)`` ns).  Parsed once per
    file."""
    return _load(path, os.path.getmtime(path))


@functools.lru_cache(maxsize=2)
def _load(path: str, _mtime: float) -> dict:
    raw = _parse(path)
    spans = raw["spans"]
    if raw["window"]:
        lo = min(s for s, _ in raw["window"])
        hi = max(e for _, e in raw["window"])
    else:
        pts = [x for sp in spans for x in sp[1:3]]
        lo, hi = (min(pts), max(pts)) if pts else (0.0, 0.0)
    spans = clip(spans, lo, hi)
    busy = Busy([(max(s, lo), min(e, hi)) for s, e in raw["busy"]
                 if e > lo and s < hi])
    return {"spans": spans, "parents": nest(spans), "busy": busy,
            "window": (lo, hi)}


def program_spans(facts: dict) -> Optional[dict]:
    """``load`` of the run's trace, or None where the run was not traced
    or the program wrote no span."""
    path = trace_path(facts)
    if path is None:
        return None
    tr = load(path)
    return tr if tr["spans"] else None


def summary(tr: dict) -> Dict[str, dict]:
    """Per span name: ``count``, ``total_s``, ``self_s`` (outside its
    children), ``idle_s`` (device idle inside it) and ``idle_self_s``
    (device idle inside it and outside its children)."""
    spans, parents, busy = tr["spans"], tr["parents"], tr["busy"]
    own = self_times(spans, parents)
    idle = [idle_inside(busy, sp[1], sp[2]) for sp in spans]
    idle_own = self_times(spans, parents,
                          measure=lambda sp: idle_inside(busy, sp[1], sp[2]))
    out: Dict[str, dict] = {}
    for i, sp in enumerate(spans):
        d = out.setdefault(sp[0], {"count": 0, "total_s": 0.0, "self_s": 0.0,
                                   "idle_s": 0.0, "idle_self_s": 0.0})
        d["count"] += 1
        d["total_s"] += (sp[2] - sp[1]) * 1e-9
        d["self_s"] += own[i] * 1e-9
        d["idle_s"] += idle[i] * 1e-9
        d["idle_self_s"] += idle_own[i] * 1e-9
    return out


# -- what the readers take -----------------------------------------------
def of(tr: dict, name: str) -> List[tuple]:
    return [sp for sp in tr["spans"] if sp[0] == name]


def stat(spans: Sequence[tuple], key: str) -> List[float]:
    return [sp[4][key] for sp in spans if key in sp[4]]


def per_step_ms(facts: dict, name: str) -> Optional[float]:
    """Host time in span ``name`` per ``session.step`` of the window, ms."""
    tr = program_spans(facts)
    if tr is None:
        return None
    hits, steps = of(tr, name), of(tr, "session.step")
    if not hits or not steps:
        return None
    return sum(e - s for _, s, e, *_ in hits) / len(steps) * 1e-6


def mean_ms(facts: dict, name: str) -> Optional[float]:
    """Mean host time of one span ``name`` in the window, ms."""
    tr = program_spans(facts)
    hits = of(tr, name) if tr is not None else []
    if not hits:
        return None
    return sum(e - s for _, s, e, *_ in hits) / len(hits) * 1e-6


def p95(xs: Sequence[float]) -> Optional[float]:
    if len(xs) < 2:
        return None
    return statistics.quantiles(xs, n=20, method="inclusive")[18]


def idle_share(facts: dict, name: str) -> Optional[float]:
    """Device idle time inside span ``name``, as % of the window."""
    tr = program_spans(facts)
    hits = of(tr, name) if tr is not None else []
    lo, hi = tr["window"] if tr is not None else (0.0, 0.0)
    if not hits or hi <= lo or not tr["busy"]:
        return None
    idle = sum(idle_inside(tr["busy"], s, e) for _, s, e, *_ in hits)
    return 100.0 * idle / (hi - lo)
