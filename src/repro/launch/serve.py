"""Closed-loop serving driver: the MCSA system serving real streams.

This is the paper's full system running end-to-end (CPU-scale), now as
a CLOSED loop (docs/ARCHITECTURE.md, "Serving data plane"):

  1. a ``repro.api`` Scenario declares the world (APs, edge servers,
     fleet, mobility, faults) plus a ``ServeConfig`` workload;
  2. the Session plans it (Li-GD splits, admission r/B budgets) and
     builds one engine pool per edge server, slots sized from the
     admitted r usage;
  3. each step, seeded Poisson arrivals hit the pools and real decode
     streams run under deadlines, backpressure, and — when the scenario
     scripts a server kill — mid-stream failover onto the planner's
     evacuation targets;
  4. ``metrics().serving`` reports the request outcomes and p50/p99
     token latency, and the baseline table (paper Figs. 3-5 quantities)
     prints next to it.

Usage:
  PYTHONPATH=src python -m repro.launch.serve                # preset
  PYTHONPATH=src python -m repro.launch.serve --scenario serve_chaos_k3
  PYTHONPATH=src python -m repro.launch.serve --failover-demo
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from repro.api import Session, get_scenario
from repro.launch.compile_cache import use_compile_cache


def _print_serving(serving: dict) -> None:
    print("== serving summary ==")
    for k in ("submitted", "completed", "device", "degraded", "lost",
              "shed", "timeouts", "retries", "relays",
              "failover_events", "tokens_emitted",
              "peak_concurrent_streams", "queue_depth_peak"):
        print(f"  {k:24s} {serving[k]}")
    for k in ("token_latency_p50_s", "token_latency_p99_s",
              "ttft_p50_s", "ttft_p99_s"):
        v = serving[k]
        print(f"  {k:24s} {v if v is None else f'{v:.3f}'}")
    print(f"  {'slots/server':24s} {serving['slots']} "
          f"({serving['servers_up']} up)")


def _failover_demo(seed: int) -> None:
    """One SplitServer stream killed mid-decode: the driver-side retry
    loop (``generate_with_failover``) relays onto a fallback and the
    report is folded into the Session's fault accounting via
    ``Session.record_failover`` — the satellite path next to the data
    plane's own failover."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config, reduced
    from repro.models import transformer as tfm
    from repro.runtime.meshenv import CPU_ENV
    from repro.serving.split import SplitServer

    cfg = reduced(get_config("starcoder2-3b"), layers=2)
    params, _ = tfm.init_lm(cfg, jax.random.PRNGKey(0), CPU_ENV)
    primary = SplitServer(cfg, params, CPU_ENV, name="edge0")
    backup = SplitServer(cfg, params, CPU_ENV, name="edge1")
    primary.fail(after_calls=3)

    sess = Session(get_scenario("serve_chaos_k3").replace(
        num_users=32, steps=1, serving=None, faults=None))
    prompt = jnp.asarray(
        np.random.default_rng(seed).integers(1, 200, (1, 6)), jnp.int32)
    toks, report = primary.generate_with_failover(
        prompt, split=1, max_new=6, fallbacks=[backup])
    sess.record_failover(report)
    fo = sess.metrics().faults["serving_failovers"]
    print(f"[failover-demo] stream survived {fo['events']} failover(s), "
          f"{fo['tokens_preserved']} token(s) preserved, "
          f"relay {fo['relay_s'] * 1e3:.2f} ms "
          f"-> tokens {np.asarray(toks)[0].tolist()}")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--scenario", default="serve_chaos_k3",
                    help="a registered preset with a ServeConfig")
    ap.add_argument("--users", type=int, default=None,
                    help="override the preset's fleet size")
    ap.add_argument("--steps", type=int, default=None,
                    help="override the preset's step count")
    ap.add_argument("--arrival-rate", type=float, default=None,
                    help="override the workload's req/s")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--failover-demo", action="store_true",
                    help="also run the SplitServer mid-stream failover "
                         "path and fold its report into the session")
    args = ap.parse_args(argv)
    use_compile_cache()

    sc = get_scenario(args.scenario)
    if sc.serving is None:
        raise SystemExit(f"scenario {sc.name!r} has no ServeConfig; "
                         f"try serve_chaos_k3")
    changes = {}
    if args.users is not None:
        changes["num_users"] = args.users
    if args.steps is not None:
        changes["steps"] = args.steps
    if args.arrival_rate is not None:
        import dataclasses
        changes["serving"] = dataclasses.replace(
            sc.serving, arrival_rate=args.arrival_rate)
    if changes:
        sc = sc.replace(**changes)

    t0 = time.time()
    sess = Session(sc)
    print(f"== {sc.name}: {sc.num_users} users, "
          f"{sess.topo.num_servers} servers, "
          f"slots {[p.slots for p in sess.dataplane.pools]} ==")
    for _ in range(sc.steps):
        rep = sess.step()
        s = rep.serving
        print(f"t={rep.t:6.0f}s handoffs={len(rep.events):4d} "
              f"active={s['active']:4d} queued={s['queued']:4d} "
              f"done={s['completed']:5d}/{s['submitted']:5d} "
              f"avail={sess.topo.availability:.2f}")
    m = sess.run(0)    # drains planner + data plane, returns metrics
    wall = time.time() - t0
    _print_serving(m.serving)
    if m.faults and "serving_failovers" in m.faults:
        print(f"  serving_failovers        {m.faults['serving_failovers']}")
    print(f"  wall                     {wall:.1f}s "
          f"(serve {sess.timings['serve_s']:.1f}s)")
    assert m.serving["lost"] == 0, "data plane lost requests"

    # baseline comparison (paper Figs. 3-5 quantities, planner accounting)
    print("\n== per-strategy mean (delay s, energy J, rent $/round) ==")
    aps = sess.topo.nearest_ap(sess.mobility.positions())
    for name in ("device_only", "edge_only", "neurosurgeon", "dnn_surgery"):
        b = sess.policy.run_baseline(name, sess.devices, aps)
        print(f"  {name:13s} T={float(np.mean(b.T)):.4f} "
              f"E={float(np.mean(b.E)):.4f} C={float(np.mean(b.C)):.6f}")

    if args.failover_demo:
        _failover_demo(args.seed)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
