"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

Reads the file with ``jax.profiler.ProfileData`` and nothing else.  Device
planes are those named ``/device:<KIND>:<n>``; on each, the line of XLA
operations (``XLA Ops``, or every line where that one is absent) gives the
intervals in which the device was busy.  Host spans are the harness's own
``jax.profiler.TraceAnnotation`` events, named ``bench.<layer>``.

All times in the returned dictionaries are seconds, measured inside the
window: the span of the harness's ``bench.window`` annotation, or the
whole trace where it is missing.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
_DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")


def find_xplane(trace_dir: str) -> str:
    """The newest ``.xplane.pb`` under ``trace_dir``."""
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def _events(line):
    return [(e.name, float(e.start_ns), float(e.duration_ns))
            for e in line.events]


def read_planes(path: str):
    """(device ops per device plane, programs per device plane, harness
    spans) from one trace file.

    Device ops and programs are ``(name, start_ns, dur_ns)``: the events
    of the ``XLA Ops`` and ``XLA Modules`` lines (every line of a device
    plane that has no ``XLA Ops`` line counts as ops); spans are the host
    events whose name starts with ``bench.``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[str, List[tuple]] = {}
    modules: Dict[str, List[tuple]] = {}
    spans: List[tuple] = []
    for plane in pd.planes:
        lines = list(plane.lines)
        if _DEVICE_PLANE.match(plane.name):
            ops = [ln for ln in lines if ln.name == "XLA Ops"] or lines
            devices[plane.name] = [ev for ln in ops for ev in _events(ln)
                                   if ev[2] > 0]
            modules[plane.name] = [ev for ln in lines
                                   if ln.name == "XLA Modules"
                                   for ev in _events(ln)]
        elif plane.name.startswith("/host:"):
            for ln in lines:
                spans.extend(ev for ev in _events(ln)
                             if ev[0].startswith(SPAN_PREFIX))
    return devices, modules, spans


def union(intervals: Sequence[Tuple[float, float]]) -> List[List[float]]:
    """Merged ``[start, end]`` intervals, sorted."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def window_of(spans, devices) -> Tuple[float, float]:
    win = [(s, s + d) for n, s, d in spans if n == WINDOW_SPAN]
    if win:
        return min(s for s, _ in win), max(e for _, e in win)
    pts = [(s, s + d) for evs in devices.values() for _, s, d in evs]
    pts += [(s, s + d) for _, s, d in spans]
    if not pts:
        raise ValueError("the trace holds no events")
    return min(s for s, _ in pts), max(e for _, e in pts)


def _label_at(t: float, spans) -> str:
    """Innermost harness span (other than the window) open at time t."""
    best = None
    for n, s, d in spans:
        if n != WINDOW_SPAN and s <= t <= s + d:
            if best is None or d < best[1]:
                best = (n, d)
    return best[0][len(SPAN_PREFIX):] if best else "outside_spans"


def reduce_trace(path: str, top: int = 10) -> dict:
    """Busy time, per-op device time and idle gaps inside the window.

    Returns ``{"window_s", "busy_s", "devices", "op_s", "op_count",
    "device_ops", "idle_gaps", "spans"}``: ``busy_s`` is the union of op
    intervals averaged over device planes; ``op_s``/``op_count`` hold the
    summed device time and event count per op name (all planes);
    ``device_ops`` the ``top`` op names by time; ``idle_gaps`` the idle
    time between ops summed by the harness span open at each gap's middle,
    the ``top`` largest; ``spans`` the harness spans as
    ``(name, seconds)`` pairs inside the window; ``module_s`` /
    ``module_count`` the device time and launches of each program (jitted
    function), by the name the trace gives it without its fingerprint
    (``jit__decode``)."""
    devices, modules, spans = read_planes(path)
    lo, hi = window_of(spans, devices)
    window_s = (hi - lo) * 1e-9
    op_s: Dict[str, float] = {}
    op_count: Dict[str, int] = {}
    busy = []
    gaps: Dict[str, float] = {}
    for evs in devices.values():
        inside = [(n, s, d) for n, s, d in evs if s + d > lo and s < hi]
        for n, s, d in inside:
            a, b = max(s, lo), min(s + d, hi)
            op_s[n] = op_s.get(n, 0.0) + (b - a) * 1e-9
            op_count[n] = op_count.get(n, 0) + 1
        merged = union(_clip([(s, s + d) for _, s, d in inside], lo, hi))
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                lab = _label_at(0.5 * (a + b), spans)
                gaps[lab] = gaps.get(lab, 0.0) + (b - a) * 1e-9 / len(devices)
    by_time = sorted(op_s.items(), key=lambda kv: -kv[1])
    module_s: Dict[str, float] = {}
    module_count: Dict[str, int] = {}
    for evs in modules.values():
        for n, s, d in evs:
            if s + d > lo and s < hi:
                name = n.split("(")[0]
                module_s[name] = module_s.get(name, 0.0) + d * 1e-9
                module_count[name] = module_count.get(name, 0) + 1
    return {
        "window_s": window_s,
        "busy_s": sum(busy) / len(busy) if busy else 0.0,
        "devices": len(devices),
        "op_s": op_s,
        "op_count": op_count,
        "module_s": module_s,
        "module_count": module_count,
        "device_ops": [[n, t] for n, t in by_time[:top]],
        "idle_gaps": [[n, t] for n, t in
                      sorted(gaps.items(), key=lambda kv: -kv[1])[:top]],
        "spans": [(n, d * 1e-9) for n, s, d in spans
                  if n != WINDOW_SPAN and s >= lo and s + d <= hi],
    }


def op_time(reduced: dict, pattern: str) -> Optional[Tuple[float, int]]:
    """(summed device seconds, event count) of ops whose name matches the
    regular expression ``pattern``; None where no op matches."""
    rx = re.compile(pattern)
    hits = [n for n in reduced["op_s"] if rx.search(n)]
    if not hits:
        return None
    return (sum(reduced["op_s"][n] for n in hits),
            sum(reduced["op_count"][n] for n in hits))


def program_time(reduced: dict, name: str) -> Optional[Tuple[float, int]]:
    """(device seconds, launches) of the program named ``name`` (for
    example ``jit__prefill``); None where it did not run."""
    if name not in reduced["module_s"]:
        return None
    return reduced["module_s"][name], reduced["module_count"][name]
