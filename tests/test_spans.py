"""Named spans inside the control step and the engine step
(:mod:`repro.telemetry.spans`): each lands in a JAX profiler trace, nested
under its parent, with its stats; ``Session.timings`` is fed by the same
blocks; and a running trace changes neither the outputs nor the programs
the engine lowers (docs/ARCHITECTURE.md, "Spans")."""
import dataclasses
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.api import Session, get_scenario
from repro.configs import get_config, reduced
from repro.models import transformer as tfm
from repro.runtime.meshenv import CPU_ENV
from repro.serving.dataplane import ServeConfig, ServingDataPlane
from repro.serving.engine import InferenceEngine
from repro.telemetry.spans import PREFIX, SPANS, span
from repro.testing.fake_engine import FakeEngine

# the spans each one may open inside (None: at the top of its thread)
PARENTS = {
    "session.plan": {None},
    "session.step": {None},
    "session.faults": {"session.step"},
    "session.on_handoffs": {"session.step"},
    "session.serve": {"session.step", None},        # None: Session.run
    "session.feedback": {"session.step"},
    "session.drain": {None, "session.step"},        # step: admission-aware
    "mobility.step": {"session.step"},
    "mobility.advance": {"mobility.step"},
    "mobility.nearest_ap": {"mobility.step"},
    "mobility.detect": {"mobility.step"},
    "planner.on_events": {"session.step"},
    "planner.fault_preamble": {"planner.on_events"},
    "planner.apply": {"planner.on_events"},
    "planner.flush": {"planner.on_events"},
    "planner.solve_prep": {"planner.on_events"},
    "planner.dispatch": {"planner.on_events"},
    "planner.admit": {"planner.on_events"},
    "planner.scatter": {"planner.on_events"},
    "engine.step": {None},
    "engine.admit": {"engine.step"},
    "engine.prefill": {"engine.admit"},
    "engine.first_token": {"engine.admit"},
    "engine.slot_write": {"engine.admit"},
    "engine.decode": {"engine.step"},
    "engine.token_pull": {"engine.step"},
    "engine.emit": {"engine.step"},
    "engine.import_cache": {None},
    "engine.cancel": {None},
}

# the stats of each span; a span not listed carries none
STATS = {
    "session.step": {"step"},
    "mobility.step": {"users"},
    "planner.on_events": {"handoffs"},
    "session.on_handoffs": {"handoffs"},
    "planner.apply": {"rows"},
    "planner.solve_prep": {"rows", "bucket"},
    "engine.step": {"active", "queued"},
    "engine.prefill": {"rid", "prompt_len", "queued_ms"},
    "engine.first_token": {"rid"},
    "engine.slot_write": {"rid"},
    "engine.cancel": {"rid"},
}


def _spans(trace_dir):
    """Every ``mcsa.*`` event of the trace under ``trace_dir`` as a dict
    (name without the prefix, start/end ns, thread, stats, parent)."""
    path = max(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for li, line in enumerate(plane.lines):
            evs = sorted(((e.name, e.start_ns, e.start_ns + e.duration_ns,
                           dict(e.stats)) for e in line.events
                          if e.name.startswith(PREFIX)),
                         key=lambda e: (e[1], -e[2]))
            stack = []
            for name, s, e, stats in evs:
                while stack and stack[-1]["end"] < e:
                    stack.pop()
                out.append({"name": name[len(PREFIX):], "start": s,
                            "end": e, "thread": (plane.name, li),
                            "stats": stats,
                            "parent": stack[-1]["name"] if stack else None})
                stack.append(out[-1])
    return out


def _traced(tmp, fn):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp), profiler_options=opts):
        result = fn()
    return result, _spans(str(tmp))


@pytest.fixture(scope="module")
def model():
    cfg = reduced(get_config("starcoder2-3b"), layers=2)
    params, _ = tfm.init_lm(cfg, jax.random.PRNGKey(0), CPU_ENV)
    return cfg, params


def _engine_run(cfg, params):
    """Three requests through a two-slot engine (the third waits in the
    queue), then one migrated out and back in, and one cancelled."""
    eng = InferenceEngine(cfg, params, slots=2, cache_len=64)
    prompts = [np.arange(1, 4, dtype=np.int32),
               np.arange(5, 10, dtype=np.int32),
               np.asarray([7, 7], np.int32)]
    rids = [eng.submit(p, max_new=4) for p in prompts]
    out = eng.run_to_completion()
    moving = eng.submit(np.asarray([3, 1, 4], np.int32), max_new=6)
    eng.step()
    eng.step()
    leaves, pos = eng.export_cache(moving)
    req = eng.requests[moving]
    done = eng.cancel(moving)
    ctx = np.concatenate([req.tokens, np.asarray(done, np.int32)])
    back = eng.import_cache(ctx, 6 - len(done), leaves, pos)
    eng.run_to_completion()
    return [out[r] for r in rids] + [done + eng.requests[back].out]


def _chaos_session():
    """``serve_chaos_k3`` at 24 users: step 0 replans handoffs, step 1
    meets the scripted server kill (evacuation through admission); the
    data plane runs fake engines and feeds the load estimator."""
    sc = get_scenario("serve_chaos_k3")
    sc = sc.replace(num_users=24, steps=2,
                    serving=dataclasses.replace(
                        sc.serving, max_requests=6, feedback=True,
                        feedback_interval=1))
    topo = sc.build_topology()
    profile = sc.build_profile()
    plane = ServingDataPlane(sc.serving, topo,
                             num_layers=profile.num_layers,
                             slots=np.full(topo.num_servers, 4),
                             engine_factory=FakeEngine)
    sess = Session(sc, topo=topo, profile=profile, dataplane=plane)
    sess.run()
    return sess


def _sync_session(policy=None):
    """``paper_fig1`` (synchronous, uncapacitated) until a step hands off."""
    sess = Session(get_scenario("paper_fig1").replace(steps=5),
                   policy=policy)
    for _ in range(5):
        if len(sess.step().events):
            break
    sess.drain()
    return sess


def _fleet_cols(sess):
    f = sess.fleet
    return {c: np.asarray(getattr(f, c)) for c in
            ("server", "split", "B", "r", "U", "T")}


@pytest.fixture(scope="module")
def traced(model, tmp_path_factory):
    """Each workload run once under a CPU profiler trace: (result, spans)."""
    runs = {
        "engine": lambda: _engine_run(*model),
        "chaos": _chaos_session,
        "sync": _sync_session,
        "baseline": lambda: _sync_session("greedy_nearest"),
    }
    return {k: _traced(tmp_path_factory.mktemp(k), fn)
            for k, fn in runs.items()}


def _all_spans(traced):
    return [s for _, spans in traced.values() for s in spans]


def test_every_span_is_emitted(traced):
    names = {s["name"] for s in _all_spans(traced)}
    assert names == set(SPANS)


@pytest.mark.parametrize("name", SPANS)
def test_span_nests_under_its_parent(traced, name):
    parents = {s["parent"] for s in _all_spans(traced) if s["name"] == name}
    assert parents and parents <= PARENTS[name], parents


@pytest.mark.parametrize("name", SPANS)
def test_span_carries_its_stats(traced, name):
    hits = [s for s in _all_spans(traced) if s["name"] == name]
    assert hits
    for s in hits:
        assert set(s["stats"]) == STATS.get(name, set()), s["stats"]
        assert all(isinstance(v, (int, float)) for v in s["stats"].values())


def test_one_admission_shares_its_rid(traced):
    spans = traced["engine"][1]
    admits = [s for s in spans if s["name"] == "engine.admit"]
    rids = set()
    for a in admits:
        inside = [s for s in spans if s["parent"] == "engine.admit"
                  and a["start"] <= s["start"] and s["end"] <= a["end"]]
        by_rid = {}
        for s in inside:
            by_rid.setdefault(s["stats"]["rid"], []).append(s["name"])
        for rid, names in by_rid.items():
            assert sorted(names) == ["engine.first_token", "engine.prefill",
                                     "engine.slot_write"], (rid, names)
        rids |= set(by_rid)
    assert rids == {0, 1, 2, 3}
    queued = [s["stats"]["queued_ms"] for s in spans
              if s["name"] == "engine.prefill"]
    assert all(q >= 0.0 for q in queued)
    # the third request waited for a slot
    assert max(queued) > min(queued)


def test_timings_are_the_summed_spans(traced):
    """``Session.timings`` keeps its keys, and each is the host-clock sum
    of the blocks its spans trace (the trace's own durations, less the
    microseconds a span takes to open and close)."""
    feeds = {"plan_s": ("session.plan",),
             "steps_s": ("mobility.step", "planner.on_events",
                         "session.on_handoffs"),
             "drain_s": ("session.drain",),
             "faults_s": ("session.faults",),
             "serve_s": ("session.serve",)}
    for run in ("chaos", "sync", "baseline"):
        sess, spans = traced[run]
        assert set(sess.timings) == set(feeds)
        for key, names in feeds.items():
            hits = [s for s in spans if s["name"] in names]
            traced_s = sum(s["end"] - s["start"] for s in hits) * 1e-9
            assert sess.timings[key] <= traced_s + 1e-6, (run, key)
            assert sess.timings[key] == pytest.approx(
                traced_s, rel=0.02, abs=1e-4 * len(hits) + 1e-6), (run, key)
        assert sess.timings["plan_s"] > 0 and sess.timings["steps_s"] > 0


def test_engine_outputs_identical_with_a_trace(traced, model):
    assert _engine_run(*model) == traced["engine"][0]


@pytest.mark.parametrize("run", ["chaos", "sync", "baseline"])
def test_session_outputs_identical_with_a_trace(traced, run):
    fresh = {"chaos": _chaos_session, "sync": _sync_session,
             "baseline": lambda: _sync_session("greedy_nearest")}[run]()
    sess = traced[run][0]
    for col, a in _fleet_cols(sess).items():
        np.testing.assert_array_equal(a, _fleet_cols(fresh)[col], col)
    ma, mb = sess.metrics(), fresh.metrics()
    for f in ("handoffs", "resplits", "relays", "mean_T", "mean_E",
              "mean_C"):
        np.testing.assert_array_equal(getattr(ma, f), getattr(mb, f), f)
    assert ma.faults == mb.faults and ma.serving == mb.serving


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_engine_programs_lower_alike_with_a_trace(model, tmp_path, program):
    cfg, params = model
    eng = InferenceEngine(cfg, params, slots=2, cache_len=64)

    def lowered():
        if program == "prefill":
            return eng._prefill_fn.lower(
                params, jnp.zeros((1, 5), jnp.int32), prompt_len=5).as_text()
        return eng._decode_fn.lower(
            params, eng.state.last_token,
            jnp.asarray(eng.state.pos, jnp.int32),
            eng.state.caches).as_text()

    plain = lowered()
    with jax.profiler.trace(str(tmp_path)):
        with span("engine.step", active=0, queued=0):
            under_trace = lowered()
    assert under_trace == plain


def test_span_into_adds_host_seconds():
    into = {"x_s": 1.0}
    with span("session.drain", into=into, key="x_s", rows=3):
        pass
    with span("session.drain", into=into, key="x_s"):
        pass
    assert 1.0 < into["x_s"] < 1.01
    # a bare span is the annotation itself: nothing is timed
    assert type(span("engine.decode")).__name__ == "TraceAnnotation"
