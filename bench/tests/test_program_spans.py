"""The readers of the program's own spans (``bench/program_spans.py`` and
the per-layer metrics built on it): the interval arithmetic on synthetic
spans, None where there is nothing to read, and a number from every
reader on a CPU trace of a tiny control step and engine step."""
import glob
import json
import os

import pytest

from bench import program_spans as ps
from bench import run

DATA = os.path.join(os.path.dirname(__file__), "data", "tiny.xplane.pb")
BENCH = run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
READERS = [m["name"] for m in BENCH["per_layer"]
           if m["name"] in ("mobility.nearest_ap_ms_per_step",
                            "planner.solve_prep_ms_per_step",
                            "planner.apply_ms_per_step",
                            "sweep.useful_row_share",
                            "engine.queue_wait_p95_ms",
                            "engine.slot_write_ms",
                            "engine.token_pull_ms",
                            "device_idle.serve.admit")]


def test_the_new_metrics_are_declared():
    assert len(READERS) == 8
    for m in BENCH["per_layer"]:
        if m["name"] in READERS:
            assert m["workloads"] and m["moves"] in {
                e["name"] for e in BENCH["end_to_end"]}


def test_nest_by_thread():
    spans = [("a", 0, 100, "t1"), ("b", 10, 40, "t1"), ("c", 50, 90, "t1"),
             ("d", 60, 70, "t1"), ("e", 20, 30, "t2")]
    assert ps.nest(spans) == [None, 0, 0, 2, None]


def test_self_times():
    spans = [("a", 0, 100, "t"), ("b", 10, 40, "t"), ("c", 50, 90, "t"),
             ("d", 60, 70, "t")]
    assert ps.self_times(spans, ps.nest(spans)) == [30, 30, 30, 10]


def test_busy_and_idle_inside_a_span():
    busy = ps.Busy([(10, 20), (15, 30), (50, 60)])
    assert ps.union([(10, 20), (15, 30), (50, 60)]) == [[10, 30], [50, 60]]
    assert busy.covered(0, 100) == 30
    assert busy.covered(25, 55) == 10
    assert busy.covered(31, 49) == 0
    assert busy.covered(12, 12) == 0
    assert ps.idle_inside(busy, 0, 100) == 70
    assert ps.idle_inside(busy, 10, 30) == 0
    assert ps.idle_inside(ps.Busy([]), 5, 8) == 3
    spans = [("a", 0, 100, "t"), ("b", 5, 35, "t")]
    own_idle = ps.self_times(spans, ps.nest(spans),
                             measure=lambda sp: ps.idle_inside(
                                 busy, sp[1], sp[2]))
    assert own_idle == [70 - 10, 10]


def test_clip_to_the_window():
    spans = [("a", 0, 10, "t"), ("b", 5, 15, "t"), ("c", 20, 30, "t")]
    assert ps.clip(spans, 8, 25) == [("a", 8, 10, "t"), ("b", 8, 15, "t"),
                                     ("c", 20, 25, "t")]


@pytest.mark.parametrize("name", READERS)
def test_reader_none_on_an_untraced_run(name):
    facts = {"steps": 3, "step_s": [0.1, 0.1, 0.1]}
    assert run.read_metric(name, facts) is None


@pytest.mark.parametrize("name", READERS)
def test_reader_none_without_program_spans(name):
    """A trace of a program that writes no ``mcsa.*`` span (a TPU trace of
    a jitted function): nothing to read."""
    facts = {"trace": {}, "trace_path": DATA, "steps": 3}
    assert run.read_metric(name, facts) is None


def _cpu_trace(trace_dir):
    """A CPU trace of two control steps of a small async fleet and a few
    engine steps, inside a ``bench.window`` span, as a traced run leaves
    it."""
    import jax
    import numpy as np
    from repro.api import Session, get_scenario
    from repro.configs import get_config, reduced
    from repro.models import transformer as tfm
    from repro.runtime.meshenv import CPU_ENV
    from repro.serving.engine import InferenceEngine

    sess = Session(get_scenario("megafleet_100k").replace(num_users=400))
    cfg = reduced(get_config("starcoder2-3b"), layers=2)
    params, _ = tfm.init_lm(cfg, jax.random.PRNGKey(0), CPU_ENV)
    eng = InferenceEngine(cfg, params, slots=2, cache_len=64)
    for step in (sess.step, sess.step, sess.drain):    # compile outside
        step()
    for s in (3, 5, 2):
        eng.submit(np.arange(1, s + 1, dtype=np.int32), 3)
    eng.run_to_completion()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(2):
            sess.step()
        sess.drain()
        for s in (3, 5, 2):
            eng.submit(np.arange(1, s + 1, dtype=np.int32), 3)
        eng.run_to_completion()
    jax.profiler.stop_trace()


@pytest.fixture(scope="module")
def cpu_trace(tmp_path_factory):
    d = tmp_path_factory.mktemp("trace")
    _cpu_trace(d)
    return glob.glob(os.path.join(str(d), "**", "*.xplane.pb"),
                     recursive=True)[0]


@pytest.mark.parametrize("name", READERS)
def test_reader_number_on_a_cpu_trace(cpu_trace, name):
    v = run.read_metric(name, {"trace": {}, "trace_path": cpu_trace})
    assert isinstance(v, float) and v >= 0.0
    if name.endswith(("_share", ".admit")):
        assert v <= 100.0


def test_trace_found_under_the_harness_directory(cpu_trace, tmp_path,
                                                 monkeypatch):
    """Without a path in the facts the reader takes the newest trace under
    the harness's trace directory, as a ``--trace 1`` run leaves it."""
    d = tmp_path / "plan.nin.vehicular" / "plugins"
    d.mkdir(parents=True)
    (d / "run.xplane.pb").write_bytes(open(cpu_trace, "rb").read())
    monkeypatch.setattr(ps, "TRACE_ROOT", str(tmp_path))
    assert ps.trace_path({"trace": {}}) == str(d / "run.xplane.pb")
    assert ps.trace_path({}) is None


def test_summary_accounts_for_the_session_step(cpu_trace):
    """The leaf spans cover the control step, and a span's own time and
    idle time never exceed its total."""
    tr = ps.load(cpu_trace)
    summ = ps.summary(tr)
    step = summ["session.step"]
    assert step["count"] == 2
    assert 0 <= step["self_s"] < 0.2 * step["total_s"]
    for d in summ.values():
        assert d["self_s"] <= d["total_s"] + 1e-9
        assert d["idle_self_s"] <= d["idle_s"] + 1e-9 <= d["total_s"] + 2e-9
    print(json.dumps({k: round(v["total_s"], 6) for k, v in summ.items()}))
