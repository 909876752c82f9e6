"""Generator of the planner cells' traffic: a fleet under random-waypoint mobility.

Traffic parameters (``bench/traffic/<name>.json``, kind ``mobility``):
``speed_range`` (m/s), ``dt`` (s per control step) and ``mobility_seed``
(the fleet's movement: every run walks the same trajectories, so every
seed gives the same handoff counts step by step); ``probe_steps`` and
``ahead_margin`` (how far set-up walks ahead of the window, below);
``check_share`` (the chance that a window step is checked against the
reference), ``check_steps`` (at most that many) and ``check_rows`` (the
rows of a checked step compared, drawn from the seed).  ``--seed`` draws
the devices' compute speeds (``c_dev``), which every replan prices, and
the rows checked.  The deployment (topology, servers, device fleet,
model, planner settings) comes from the configuration.

Set-up builds the topology and the device fleet from the configuration
and the seed and hands them to ``repro.api.Session``, which makes the
static Li-GD plan.  The planner dispatches eager operations on arrays
sized by each step's handoff count, so every new count compiles programs
of its own.  Set-up therefore copies the session and walks the copy
ahead along the very trajectory the window will take: ``probe_steps``
steps, which the session then repeats warm to time one step, and then as
many more as ``ahead_margin`` times the window's length at that pace.
The window then calls ``Session.step`` on the session until ``--seconds``
have passed, with every program it runs already compiled (or loaded from
the persistent cache), and closes with ``Session.drain``, so the last
dispatched solve lies inside it.

The check compares, for the sampled steps, every row the step replanned
with the plain reference (``bench/reference/planner.py``).
"""
from __future__ import annotations

import copy
import math
import time

import numpy as np

from bench.reference import planner as ref

PLAN_COLS = ("server", "split", "B", "r", "U", "R")


def build(ctx):
    """(session, c_dev) for the cell's configuration and seed."""
    from repro.api import Scenario, Session
    from repro.core.costs import DeviceFleet, EdgeParams
    from repro.core.ligd import LiGDConfig
    from repro.core.network import build_topology

    cfg, tr, seed = ctx["config"], ctx["traffic"], ctx["seed"]
    topo_c, pl = cfg["topology"], cfg["planner"]
    topo = build_topology(
        topo_c["num_aps"], topo_c["num_servers"], area=topo_c["area"],
        seed=topo_c["topo_seed"], heterogeneity=topo_c["heterogeneity"],
        edge_params=[EdgeParams(**s) for s in topo_c["servers"]])
    n = cfg["fleet"]["num_users"]
    c_dev = np.random.default_rng([seed, 1]).uniform(
        *cfg["fleet"]["c_dev_range"], n)
    dev = {k: cfg["devices"][k] for k in ref.DEVICE_FIELDS if k != "c_dev"}
    devices = DeviceFleet(num_users=n, c_dev=c_dev, **dev)
    scenario = Scenario(
        name=ctx["workload"]["name"], num_aps=topo_c["num_aps"],
        num_servers=topo_c["num_servers"], area=topo_c["area"],
        topo_seed=topo_c["topo_seed"],
        heterogeneity=topo_c["heterogeneity"], model=pl["model_id"],
        model_seq=pl.get("model_seq", 128), num_users=n,
        mobility="random_waypoint", speed_range=tuple(tr["speed_range"]),
        mobility_seed=int(tr["mobility_seed"]),
        ligd=LiGDConfig(**dict(pl["ligd"], init=tuple(pl["ligd"]["init"]))),
        candidates_k=pl["candidates_k"],
        async_replanning=pl["async_replanning"], dt=tr["dt"])
    return Session(scenario, topo=topo, devices=devices), c_dev


def _rows_of(fleet, users):
    return {c: np.array(getattr(fleet, c)[users]) for c in PLAN_COLS}


def _spans(session, times):
    """Wrap the session's public mobility and policy components in harness
    spans, each call timed by the host clock (two clock reads a step)."""
    import jax
    mob_step = session.mobility.step
    on_events = session.policy.on_events

    def timed(fn, key, label):
        def wrapper(*a, **kw):
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation(label):
                out = fn(*a, **kw)
            times[key].append(time.perf_counter() - t0)
            return out
        return wrapper

    session.mobility.step = timed(mob_step, "mobility_s", "bench.mobility")
    session.policy.on_events = timed(on_events, "on_events_s",
                                     "bench.on_events")


def walk_ahead(session, tr, seconds, counter, log) -> dict:
    """Compile every program the window will run: walk a copy of the
    session ``probe_steps`` steps ahead, repeat them on the session to
    time a warm step (the fastest of them), then walk the copy on for ``ahead_margin`` times
    the steps the window can take at that pace, and drain it.  Returns
    the steps the copy walked and the programs it built (compiled or
    loaded from the persistent cache) on the way."""
    shadow = copy.deepcopy(session)
    probe = int(tr["probe_steps"])
    c0, h0, b0 = counter.compiles, counter.cache_hits, counter.built
    t0 = time.perf_counter()
    for _ in range(probe):
        shadow.step()
    t1 = time.perf_counter()
    warm = float("inf")
    for _ in range(probe):
        ts = time.perf_counter()
        session.step()
        warm = min(warm, time.perf_counter() - ts)
    more = math.ceil(tr["ahead_margin"] * seconds / warm)
    t2 = time.perf_counter()
    for _ in range(more):
        shadow.step()
    shadow.drain()
    t3 = time.perf_counter()
    log(f"walk ahead: {probe} + {more} steps, copy {(t1 - t0) / probe:.3f}"
        f" then {(t3 - t2) / max(more, 1):.3f} s a step, warm step "
        f"{warm:.3f} s; {counter.compiles - c0} compiles, "
        f"{counter.cache_hits - h0} cache loads")
    del shadow
    return {"steps": probe + more,
            "programs": counter.built - b0}


def run(ctx) -> dict:
    import jax
    from repro.core.mobility import HandoffBatch
    tr, cfg = ctx["traffic"], ctx["config"]
    hooks, log = ctx["hooks"], ctx["log"]
    t0 = time.perf_counter()
    session, c_dev = build(ctx)
    t_build = time.perf_counter() - t0
    walk = walk_ahead(session, tr, ctx["seconds"], ctx["compiles"], log)
    if "session" in hooks:
        hooks["session"](session)
    jax.block_until_ready(jax.numpy.zeros(()))
    setup_s = ctx["setup_clock"]()
    log(f"setup: session {t_build:.2f} s, walk ahead "
        f"{time.perf_counter() - t0 - t_build:.2f} s ({walk['steps']} steps)")

    sync = not cfg["planner"]["async_replanning"]
    rng = np.random.default_rng([ctx["seed"], 2])
    share, max_checks = tr["check_share"], int(tr["check_steps"])
    check_rows = int(tr["check_rows"])
    checks, awaiting = [], []
    times = {"step_s": [], "mobility_s": [], "on_events_s": []}
    tracing = ctx["trace_dir"] is not None
    _spans(session, times)
    if tracing:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(ctx["trace_dir"], profiler_options=opts)
    window = jax.profiler.TraceAnnotation("bench.window")
    counter = ctx["compiles"]
    counter.begin_window()
    window.__enter__()
    t_start = time.perf_counter()
    steps = 0
    while time.perf_counter() - t_start < ctx["seconds"]:
        sample = len(checks) + len(awaiting) < max_checks and \
            rng.random() < share
        pre = None
        if sample and sync:
            pre = {c: np.array(getattr(session.fleet, c)) for c in PLAN_COLS}
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.session_step"):
            rep = session.step()
        times["step_s"].append(time.perf_counter() - t0)
        steps += 1
        ev = rep.events
        if len(ev):
            # an async replan dispatched by an earlier step was applied
            # at the start of this one: its rows now hold its plans
            for item in awaiting:
                item["post"] = _rows_of(session.fleet, item["events"].user)
            checks.extend(awaiting)
            awaiting = []
        if sample and len(ev):
            keep = np.sort(rng.choice(len(ev), min(len(ev), check_rows),
                                      replace=False))
            ev = HandoffBatch(t=ev.t, user=ev.user[keep],
                              old_server=ev.old_server[keep],
                              new_server=ev.new_server[keep],
                              new_ap=ev.new_ap[keep],
                              hops_new=ev.hops_new[keep],
                              hops_back=ev.hops_back[keep])
            item = {"events": ev}
            if sync:
                item["pre"] = {c: v[ev.user] for c, v in pre.items()}
                item["post"] = _rows_of(session.fleet, ev.user)
                checks.append(item)
            else:
                item["pre"] = _rows_of(session.fleet, ev.user)
                awaiting.append(item)
    with jax.profiler.TraceAnnotation("bench.drain"):
        session.drain()
    t_end = time.perf_counter()
    window.__exit__(None, None, None)
    counter.end_window()
    if tracing:
        jax.profiler.stop_trace()
    for item in awaiting:
        item["post"] = _rows_of(session.fleet, item["events"].user)
    checks.extend(awaiting)

    peak = ctx["memory_peak_bytes"]()
    log(f"window: {steps} steps, {session.total_handoffs} handoffs in all; "
        f"a step: mobility {np.mean(times['mobility_s']) * 1e3:.2f} ms, "
        f"on_events {np.mean(times['on_events_s']) * 1e3:.2f} ms")
    t_ag = float(session.policy.t_ag_estimate)
    handoffs = int(session.total_handoffs)
    del session
    out = {
        "end_to_end": {"control_step_ms": (t_end - t_start) / steps * 1e3,
                       "setup_s": setup_s},
        "attempted": steps, "failed": 0,
        "checks": compare(cfg, checks, c_dev, t_ag),
        "memory_peak_bytes": peak,
        "facts": {"steps": steps, "handoffs": handoffs, "walk": walk,
                  "window_s": t_end - t_start, **times},
    }
    if hooks.get("control"):
        out["control"] = compare(cfg, checks, c_dev, t_ag, control=True)
    return out


def control_plan(p, prof, ligd, ev, pre):
    """The control: the reference replan computed in bfloat16, one step
    below the float32 the planner states, put in the program's place."""
    import ml_dtypes
    dt = ml_dtypes.bfloat16
    pb = {k: (v.astype(dt) if v.dtype.kind == "f" else v)
          for k, v in p.items()}
    plan = ref.mligd(pb, prof, ligd, dt=dt)
    relay = plan["R"] == 1
    return {"R": plan["R"], "split": plan["split"],
            "B": plan["B"].astype(np.float64),
            "r": plan["r"].astype(np.float64),
            "server": np.where(relay, pre["server"], ev.new_server)}


def check_rows(cfg, item, c_dev, t_ag, control=False):
    """(relative gaps, misrouted mask) of one checked step's rows: the
    program's plan (or the control's), priced by the reference, against
    the reference's own replan of the same rows from the same frozen
    plans."""
    ev, pre, post = item["events"], item["pre"], item["post"]
    prof = ref.layer_profile(cfg["model"], cfg["planner"])
    p = ref.rows(cfg, prof, c_dev[ev.user], ev.hops_new, ev.hops_back,
                 ev.new_server, pre["server"], pre["split"], pre["r"],
                 pre["B"], t_ag)
    best = ref.mligd(p, prof, cfg["planner"]["ligd"])
    if control:
        post = control_plan(p, prof, cfg["planner"]["ligd"], ev, pre)
    relay = post["R"] == 1
    misrouted = np.where(relay, (post["server"] != pre["server"])
                         | (post["split"] != pre["split"]),
                         post["server"] != ev.new_server)
    u = ref.evaluate(p, prof, post)
    return (u - best["U"]) / np.abs(best["U"]), misrouted


def compare(cfg, checks, c_dev, t_ag, control=False) -> list:
    """The numbers compared: rows checked; misrouted rows (a re-split not
    on the new server, or a relay that left the original server or
    split), an exact comparison; and the relative gaps of the other rows,
    at their 99th percentile and at their widest, each where the
    configuration gives it a limit."""
    lim = cfg["check"]
    gaps, mis, n = [], 0, 0
    for item in checks:
        g, m = check_rows(cfg, item, c_dev, t_ag, control)
        gaps.append(g[~m])
        mis += int(m.sum())
        n += len(g)
    g = np.concatenate(gaps) if gaps else np.zeros(0)
    values = {"misrouted_rows": mis,
              "plan_gap_p99": float(np.quantile(g, 0.99)) if len(g)
              else float("inf"),
              "plan_gap_max": float(np.max(g)) if len(g) else float("inf")}
    out = [{"name": "rows_checked", "value": n, "limit": 1, "ok": n >= 1}]
    for name, v in values.items():
        if name in lim:
            out.append({"name": name, "value": v, "limit": lim[name],
                        "ok": bool(np.isfinite(v)) and v <= lim[name]})
    return out
