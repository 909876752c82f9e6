"""The trace reduction on a small trace recorded on a TPU v5e: three
launches of a jitted function, each inside a ``bench.tiny`` span."""
import os

import pytest

from bench import trace

DATA = os.path.join(os.path.dirname(__file__), "data", "tiny.xplane.pb")


def test_union_merges_overlaps():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]


def test_reduce_recorded_trace():
    red = trace.reduce_trace(DATA)
    assert red["devices"] == 1
    assert 0 < red["busy_s"] < red["window_s"]
    secs, launches = trace.program_time(red, "jit_tiny")
    # a program's span holds its ops and the short gaps between them
    assert launches == 3
    assert secs == pytest.approx(red["busy_s"], rel=0.05)
    # every op of the program, and nothing else, ran on the device
    assert sum(red["op_s"].values()) >= red["busy_s"] - 1e-12
    assert trace.op_time(red, r"fusion") is not None
    assert trace.op_time(red, r"no_such_kernel") is None
    assert trace.program_time(red, "jit_other") is None
    idle = sum(t for _, t in red["idle_gaps"])
    assert idle == pytest.approx(red["window_s"] - red["busy_s"], rel=1e-6)
    assert {n for n, _ in red["spans"]} == {"bench.tiny"}
    assert len(red["device_ops"]) <= 10


def test_idle_gaps_named_by_open_span():
    spans = [("bench.window", 0.0, 100.0), ("bench.mobility", 10.0, 20.0)]
    assert trace._label_at(15.0, spans) == "mobility"
    assert trace._label_at(50.0, spans) == "outside_spans"
