"""FleetState planner: the vectorized handoff path must reproduce the
seed's per-event bookkeeping exactly (both MLi-GD branches), the solver
caches must key on profile CONTENT, and the padded-batch bucketing must
not leak padding into results."""
import copy
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.chain_cnns import nin, vgg16
from repro.core import ligd as ligd_mod
from repro.core import mligd as mligd_mod
from repro.core import planner as planner_mod
from repro.core.costs import (DeviceFleet, DeviceParams, EdgeParams,
                              LayerProfile, dev_dict, edge_dict,
                              gather_devices, stack_devices, stack_edges)
from repro.core.ligd import LiGDConfig, LiGDResult, solve_ligd_batch_jit
from repro.core.mligd import orig_strategy_dict, solve_mligd_batch_jit
from repro.core.mobility import HandoffBatch, RandomWaypointMobility
from repro.core.network import build_topology
from repro.core.planner import MCSAPlanner, _pow2_bucket
from repro.core.profile import profile_of

CFG = LiGDConfig(max_iters=150)


def _hetero_topo(**kw):
    """Fixed topology with one strong/cheap and one weak/expensive server
    so crafted handoffs exercise BOTH MLi-GD branches."""
    edges = [
        EdgeParams(),                                        # 0: original
        EdgeParams(c_min=2e9, rho_min=5e-3, r_max=4.0),      # 1: weak
        EdgeParams(c_min=500e9, rho_min=1e-5, r_max=64.0),   # 2: strong
    ]
    return build_topology(16, 3, seed=0, edge_params=edges, **kw)


def _seed_reference_on_handoffs(planner, batch, devices, fleet_before):
    """The seed planner's per-event path, verbatim: per-event Python loop
    building origs/devs lists, one batched MLi-GD solve, per-event plan
    updates.  Returns (MLiGDResult, list of updated UserPlan views)."""
    plans = [fleet_before[i] for i in range(len(fleet_before))]
    devs, edges_new, origs, hops_back = [], [], [], []
    for ev in batch:
        d = devices[ev.user]
        devs.append(dataclasses.replace(
            d, hops=ev.hops_new, t_ag=planner.t_ag_estimate))
        edges_new.append(planner.topo.edges[ev.new_server])
        plan = plans[ev.user]
        orig_edge = edge_dict(planner.topo.edges[plan.server])
        prev = LiGDResult(
            split=jnp.asarray(plan.split), B=jnp.asarray(plan.B),
            r=jnp.asarray(plan.r), U=jnp.asarray(plan.U),
            T=jnp.asarray(plan.T), E=jnp.asarray(plan.E),
            C=jnp.asarray(plan.C), iters_per_layer=jnp.zeros(1),
            U_per_layer=jnp.zeros(1), B_per_layer=jnp.zeros(1),
            r_per_layer=jnp.zeros(1))
        origs.append(orig_strategy_dict(planner.profile, orig_edge, prev))
        hops_back.append(float(ev.hops_back))
    devs_s = stack_devices(devs)
    edges_s = stack_edges(edges_new)
    origs_s = jax.tree.map(lambda *xs: jnp.stack(xs), *origs)
    res = solve_mligd_batch_jit(planner.profile, devs_s, edges_s, origs_s,
                                jnp.asarray(hops_back, jnp.float32),
                                planner.cfg)
    for i, ev in enumerate(batch):
        take_back = bool(res.R[i])
        plans[ev.user] = dataclasses.replace(
            plans[ev.user],
            server=plans[ev.user].server if take_back else ev.new_server,
            split=int(res.split[i]), B=float(res.B[i]), r=float(res.r[i]),
            U=float(res.U[i]), T=float(res.T[i]), E=float(res.E[i]),
            C=float(res.C[i]), R=int(res.R[i]))
    return res, plans


def _crafted_batch(topo, servers0):
    """Handoffs that force both branches: users 0/1 walk into the WEAK
    server's coverage far from home (relay-back should win for at least
    one), users 2/3 walk into the STRONG server next door (re-split)."""
    user = np.asarray([0, 1, 2, 3])
    new_server = np.asarray([1, 1, 2, 2])
    return HandoffBatch(
        t=0.0, user=user,
        old_server=servers0[user].astype(np.int64),
        new_server=new_server.astype(np.int64),
        new_ap=topo.server_aps[new_server].astype(np.int64),
        hops_new=np.asarray([0, 0, 0, 0], np.int64),
        hops_back=np.asarray([1, 2, 6, 8], np.int64))


@pytest.mark.parametrize("model", [nin, vgg16])
def test_vectorized_on_handoffs_matches_seed_per_event(model):
    topo = _hetero_topo()
    prof = profile_of(model())
    planner = MCSAPlanner(prof, topo, CFG)
    devices = [DeviceParams(c_dev=c) for c in np.linspace(3e9, 30e9, 6)]
    aps = topo.nearest_ap(np.tile(topo.ap_xy[topo.server_aps[0]], (6, 1)))
    _, servers0, fleet = planner.plan_static(devices, aps)
    batch = _crafted_batch(topo, servers0)

    before = copy.deepcopy(fleet)
    ref_res, ref_plans = _seed_reference_on_handoffs(
        planner, batch, devices, before)
    res = planner.on_handoffs(batch, devices, fleet)

    # both branches must actually be exercised by the crafted batch
    R = np.asarray(ref_res.R)
    assert R.min() == 0 and R.max() == 1, R

    np.testing.assert_array_equal(np.asarray(res.R), R)
    np.testing.assert_array_equal(np.asarray(res.split),
                                  np.asarray(ref_res.split))
    for f in ("B", "r", "U", "T", "E", "C"):
        np.testing.assert_allclose(np.asarray(getattr(res, f)),
                                   np.asarray(getattr(ref_res, f)),
                                   rtol=1e-5)
    # ...and the scattered fleet table matches the per-event plan updates
    for i in range(len(fleet)):
        p, q = ref_plans[i], fleet[i]
        assert (p.server, p.split, p.R) == (q.server, q.split, q.R), i
        for f in ("B", "r", "U", "T", "E", "C"):
            assert getattr(p, f) == pytest.approx(getattr(q, f),
                                                  rel=1e-5, abs=1e-12), (i, f)


def test_on_handoffs_from_mobility_batch():
    """End-to-end: array handoffs straight from the vectorized waypoint
    model drive the planner without any event objects."""
    topo = build_topology(16, 4, seed=0)
    prof = profile_of(nin())
    planner = MCSAPlanner(prof, topo, CFG)
    fleet_devs = DeviceFleet(
        c_dev=np.random.default_rng(0).uniform(3e9, 8e9, 32))
    mob = RandomWaypointMobility(topo, 32, seed=3, speed_range=(10., 30.))
    _, _, fleet = planner.plan_static(fleet_devs,
                                      topo.nearest_ap(mob.positions()))
    total = 0
    for t in range(120):
        batch = mob.step(10.0, t * 10.0)
        if not batch:
            continue
        res = planner.on_handoffs(batch, fleet_devs, fleet)
        total += len(batch)
        assert np.asarray(res.R).shape == (len(batch),)
        assert set(np.asarray(res.R)) <= {0, 1}
        moved = batch.user
        # R=0 users now sit on their new server; R=1 kept the original
        resplit = np.asarray(res.R) == 0
        np.testing.assert_array_equal(fleet.server[moved][resplit],
                                      batch.new_server[resplit])
        if total >= 8:
            break
    assert total > 0


def test_profile_cache_keys_on_content_not_identity():
    prof_a = profile_of(nin())
    prof_b = LayerProfile(name=prof_a.name,
                          flops=prof_a.flops * 2.0,
                          out_bits=prof_a.out_bits,
                          in_bits=prof_a.in_bits,
                          result_bits=prof_a.result_bits)
    assert prof_a.fingerprint != prof_b.fingerprint
    # content-identical profile at a different id() shares the entry
    prof_a2 = LayerProfile(name=prof_a.name, flops=prof_a.flops.copy(),
                           out_bits=prof_a.out_bits.copy(),
                           in_bits=prof_a.in_bits,
                           result_bits=prof_a.result_bits)
    assert prof_a.fingerprint == prof_a2.fingerprint

    devs = stack_devices([DeviceParams(), DeviceParams(c_dev=40e9)])
    edge = edge_dict(EdgeParams())
    before = len(ligd_mod._PROFILE_CACHE)
    res_a = solve_ligd_batch_jit(prof_a, devs, edge, CFG)
    mid = len(ligd_mod._PROFILE_CACHE)
    res_b = solve_ligd_batch_jit(prof_b, devs, edge, CFG)
    res_a2 = solve_ligd_batch_jit(prof_a2, devs, edge, CFG)
    after = len(ligd_mod._PROFILE_CACHE)
    assert mid == before + 1
    assert after == mid + 1          # prof_b new entry, prof_a2 shared
    # distinct content must give distinct solutions (2x flops shifts U)
    assert not np.allclose(np.asarray(res_a.U), np.asarray(res_b.U))
    np.testing.assert_allclose(np.asarray(res_a.U), np.asarray(res_a2.U))


def test_handoff_batches_bucket_to_pow2_jit_shapes():
    assert _pow2_bucket(1) == 8 and _pow2_bucket(8) == 8
    assert _pow2_bucket(9) == 16 and _pow2_bucket(1000) == 1024

    topo = _hetero_topo()
    prof = profile_of(nin())
    planner = MCSAPlanner(prof, topo, CFG)
    devices = DeviceFleet(c_dev=np.linspace(3e9, 8e9, 24))
    aps = topo.nearest_ap(np.tile(topo.ap_xy[topo.server_aps[0]], (24, 1)))
    _, servers0, fleet = planner.plan_static(devices, aps)

    mligd_mod._CACHE.clear()
    shapes = set()
    rng = np.random.default_rng(0)
    for n in (1, 3, 5, 7, 2, 6, 4, 8):
        user = rng.choice(24, n, replace=False)
        batch = HandoffBatch(
            t=0.0, user=user,
            old_server=fleet.server[user],
            new_server=np.full(n, 1, np.int64),
            new_ap=np.full(n, topo.server_aps[1], np.int64),
            hops_new=np.zeros(n, np.int64),
            hops_back=np.full(n, 2, np.int64))
        res = planner.on_handoffs(batch, devices, fleet)
        assert np.asarray(res.R).shape == (n,)
        shapes.add(_pow2_bucket(n))
    # eight distinct event counts, ONE padded solve shape
    assert shapes == {8}


def _random_handoffs(topo, fleet, rng, n):
    """``n`` handoffs of distinct users to random servers, with the hop
    counts the topology gives."""
    user = rng.choice(len(fleet), n, replace=False)
    new_server = rng.integers(0, topo.num_servers, n).astype(np.int64)
    new_ap = topo.server_aps[new_server].astype(np.int64)
    old = fleet.server[user].astype(np.int64)
    return HandoffBatch(
        t=0.0, user=user, old_server=old, new_server=new_server,
        new_ap=new_ap,
        hops_new=topo.hops[new_ap, new_server].astype(np.int64),
        hops_back=topo.hops[new_ap, old].astype(np.int64))


def _replan_setup(mode):
    """(planner, devices, fleet) of a 24-user fleet on the hetero topology:
    K=1 uncapacitated for ``sync``/``async``, K=3 under compute budgets
    (the ledger-aware admission path) for ``capacitated_k3``."""
    k3 = mode == "capacitated_k3"
    topo = _hetero_topo(r_capacity=60.0) if k3 else _hetero_topo()
    planner = MCSAPlanner(profile_of(nin()), topo, CFG,
                          candidates_k=3 if k3 else 1,
                          async_replanning=mode == "async")
    devices = DeviceFleet(c_dev=np.linspace(3e9, 8e9, 24))
    aps = np.arange(24) % topo.num_aps
    _, _, fleet = planner.plan_static(devices, aps)
    return planner, devices, fleet


@pytest.mark.parametrize("mode,counts", [
    ("sync", (9, 11, 13, 15, 10, 12, 14, 16)),
    ("async", (9, 11, 13, 15, 10, 12, 14, 16)),
    # n·K rows: 18..30, all in the 32 bucket
    ("capacitated_k3", (6, 7, 8, 9, 10)),
])
def test_handoff_counts_in_one_bucket_build_no_programs(mode, counts):
    """Every input of the replan is padded to its bucket on the host and
    cut back on the host: once a bucket's solve is built, a new handoff
    count in the same bucket builds no device program at all (compiled
    or loaded from the persistent cache), the apply included."""
    from jax import monitoring
    planner, devices, fleet = _replan_setup(mode)
    K = planner.candidates_k
    assert len({_pow2_bucket(n * K) for n in counts}) == 1
    built = []

    def on_duration(event, duration, **_):
        if event.endswith("backend_compile_duration"):
            built.append(event)

    def on_event(event, **_):
        if event.endswith("compilation_cache/cache_hits"):
            built.append(event)

    rng = np.random.default_rng(1)
    per_count = []
    jax.clear_caches()      # no shape met by an earlier test is cached
    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)
    try:
        for n in counts:
            before = len(built)
            res = planner.on_handoffs(_random_handoffs(
                planner.topo, fleet, rng, n), devices, fleet)
            if mode != "async":
                assert np.asarray(res.R).shape == (n,)
            per_count.append(len(built) - before)
        before = len(built)
        planner.drain(fleet)
        per_count.append(len(built) - before)
    finally:
        monitoring.unregister_event_duration_listener(on_duration)
        monitoring.unregister_event_listener(on_event)
    assert per_count[1:] == [0] * len(counts), per_count


def _direct_rows(planner, devices, fleet, users, new_srv, hops_new,
                 hops_back):
    """MLi-GD over exactly these rows, unpadded, with inputs built by the
    device-side helpers and the rent priced inside the jit."""
    f_l, f_e, w = planner.profile.prefix_tables()
    s = fleet.split[users]
    r = fleet.r[users]
    devs = gather_devices(devices, users)
    devs["hops"] = jnp.asarray(hops_new, jnp.float32)
    devs["t_ag"] = jnp.full(len(users), planner.t_ag_estimate, jnp.float32)
    origs = {"split": jnp.asarray(s, jnp.int32),
             "f_l": jnp.asarray(f_l[s], jnp.float32),
             "f_e": jnp.asarray(f_e[s], jnp.float32),
             "w": jnp.asarray(w[s], jnp.float32),
             "r": jnp.asarray(np.where(r > 0, r, 1.0), jnp.float32),
             "B": jnp.asarray(fleet.B[users], jnp.float32),
             "r_true": jnp.asarray(r, jnp.float32)}
    edge_orig = {k: v for k, v in
                 planner._edges_for(fleet.server[users]).items()
                 if k in ("rho_min", "rho_B", "B0", "gamma_B")}
    res = solve_mligd_batch_jit(
        planner.profile, devs, planner._edges_for(new_srv), origs,
        jnp.asarray(hops_back, jnp.float32), planner.cfg,
        edge_orig=edge_orig)
    return jax.tree.map(np.asarray, res)


@pytest.mark.parametrize("mode", ["async", "sync", "capacitated_k3"])
def test_replan_scatters_the_direct_solve_rows(mode, monkeypatch):
    """The bucket-padded replan scatters the rows a direct solve over the
    unpadded rows gives: split, R and server exactly, U/B/r to float32
    round-off — through the async K=1 apply, the sync scatter and the
    K=3 ledger-aware admission."""
    planner, devices, fleet = _replan_setup(mode)
    before = copy.deepcopy(fleet)
    batch = _random_handoffs(planner.topo, fleet,
                             np.random.default_rng(2), 11)
    n, u = len(batch), batch.user
    K = planner.candidates_k
    if K == 1:
        cand = batch.new_server[:, None]
        hops_new = batch.hops_new
    else:
        cand = planner.topo.candidates(K)[batch.new_ap]
        hops_new = planner.topo.hops[batch.new_ap[:, None], cand]
    direct = _direct_rows(planner, devices, before, np.repeat(u, K),
                          cand.reshape(-1), hops_new.reshape(-1),
                          np.repeat(batch.hops_back, K))

    reports = []
    waterfill = planner_mod.admit_waterfill

    def recording(*a, **kw):
        reports.append(waterfill(*a, **kw))
        return reports[-1]

    monkeypatch.setattr(planner_mod, "admit_waterfill", recording)
    res = planner.on_handoffs(batch, devices, fleet)
    if mode == "async":
        np.testing.assert_array_equal(fleet.split[u], before.split[u])
        res = planner.drain(fleet)
    if K == 1:
        pick = np.arange(n)
    else:
        (report,) = reports
        assert not report.rejected.any()
        pick = np.arange(n) * K + report.choice
    exp = jax.tree.map(lambda a: a[pick], direct)
    offl = exp.split < planner.profile.num_layers
    server = np.where(exp.R == 1, before.server[u],
                      cand[np.arange(n), pick % K])
    assert 0 < exp.R.sum() < n       # both branches taken
    for got in (res, fleet):
        rows = slice(None) if got is res else u
        np.testing.assert_array_equal(np.asarray(got.split)[rows],
                                      exp.split)
        np.testing.assert_array_equal(np.asarray(got.R)[rows], exp.R)
        np.testing.assert_allclose(np.asarray(got.U)[rows], exp.U,
                                   rtol=2e-7)
        for f in ("B", "r"):
            want = getattr(exp, f) if K == 1 \
                else np.where(offl, getattr(exp, f), 0.0)
            np.testing.assert_allclose(np.asarray(getattr(got, f))[rows],
                                       want, rtol=2e-7)
    np.testing.assert_array_equal(fleet.server[u], server)


def test_plan_static_sharded_matches_default():
    """shard_map data-parallel solve == single-device solve.  Needs >1
    device, so it forces a 2-device host platform in a subprocess (the
    suite itself must see the real single CPU device — see conftest)."""
    import os
    import subprocess
    import sys
    script = r"""
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=2")
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
import numpy as np
from repro.configs.chain_cnns import nin
from repro.core.costs import DeviceFleet
from repro.core.ligd import LiGDConfig
from repro.core.network import build_topology
from repro.core.planner import MCSAPlanner
from repro.core.profile import profile_of
from repro.runtime.meshenv import make_env

assert jax.device_count() == 2
topo = build_topology(16, 4, seed=0)
prof = profile_of(nin())
cfg = LiGDConfig(max_iters=60)
devices = DeviceFleet(c_dev=np.linspace(3e9, 8e9, 8))
aps = np.arange(8) % topo.num_aps
mesh = jax.make_mesh((2,), ("data",))
env = make_env(mesh)
assert env.dp == 2

res_ref, _, _ = MCSAPlanner(prof, topo, cfg).plan_static(devices, aps)
res_sh, _, _ = MCSAPlanner(prof, topo, cfg).plan_static(devices, aps,
                                                        env=env)
np.testing.assert_array_equal(np.asarray(res_ref.split),
                              np.asarray(res_sh.split))
for f in ("B", "r", "U", "T", "E", "C"):
    np.testing.assert_allclose(np.asarray(getattr(res_ref, f)),
                               np.asarray(getattr(res_sh, f)),
                               rtol=1e-5)
print("SHARDED_OK")
"""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = (os.path.join(root, "src") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    out = subprocess.run([sys.executable, "-c", script], cwd=root,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "SHARDED_OK" in out.stdout


def test_duplicate_users_in_batch_last_event_wins():
    """Both paths agree when the LAST duplicate event decides R=0 (or all
    relay): origs always come from pre-call state in both.  (When an
    earlier duplicate re-splits and a later one relays back, the
    vectorized path restores the pre-call server its frozen strategy was
    priced against — documented in on_handoffs — while the seed kept the
    earlier event's server; that combination is deliberately not compared
    here.)"""
    topo = _hetero_topo()
    prof = profile_of(nin())
    planner = MCSAPlanner(prof, topo, CFG)
    devices = [DeviceParams() for _ in range(4)]
    aps = topo.nearest_ap(np.tile(topo.ap_xy[topo.server_aps[0]], (4, 1)))
    _, servers0, fleet = planner.plan_static(devices, aps)
    batch = HandoffBatch(
        t=0.0, user=np.asarray([0, 0]),
        old_server=fleet.server[[0, 0]],
        new_server=np.asarray([1, 2], np.int64),
        new_ap=topo.server_aps[[1, 2]].astype(np.int64),
        hops_new=np.asarray([0, 0], np.int64),
        hops_back=np.asarray([2, 6], np.int64))
    before = copy.deepcopy(fleet)
    ref_res, ref_plans = _seed_reference_on_handoffs(
        planner, batch, devices, before)
    planner.on_handoffs(batch, devices, fleet)
    p, q = ref_plans[0], fleet[0]
    assert (p.server, p.split, p.R) == (q.server, q.split, q.R)
    assert p.U == pytest.approx(q.U, rel=1e-5)
