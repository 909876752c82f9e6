"""MCSA planner: ties the Li-GD/MLi-GD solvers to a concrete network of
users, APs, and heterogeneous edge servers (the full system of Fig. 1).

Responsibilities:
  * static planning — per-user (s, B, r) via batched Li-GD against each
    user's serving edge server (per-user edge params gathered from a
    per-topology table, solved in one vectorized call);
  * incremental replanning — handoffs, fault evacuations, and capacity
    drains all enqueue into one dirty set (``repro.core.events``) and
    are re-solved by ONE fused MLi-GD solve per step over only the
    dirty rows, with a sparse scatter into the fleet table
    (docs/ARCHITECTURE.md, "Event lifecycle");
  * strategy-calculation-time feedback — measured solver time feeds the
    CBR term T_Ag/k of the *next* solve (Eq. 6/7's self-consistency).

Both solve paths dispatch on ``LiGDConfig.solver``: the default
``"fused"`` routes the whole control plane through the fused whole-sweep
solver in ``repro.kernels.ligd_step`` (Pallas kernel on TPU, masked-JAX
ref on CPU/GPU; per-user edge rows mean heterogeneous servers still take
ONE launch); ``solver="autodiff"`` restores the vmapped autodiff oracle.
See the kernel package docstring for the selection rules.

Plans live in :class:`FleetState`, a struct-of-arrays table (one (X,)
array per quantity), so planning X users costs O(fields) Python plus one
jitted solve — never O(X) interpreter work.  Handoff batches are padded
to power-of-two sizes on the host before the jitted MLi-GD solve, and cut
back on the host where the result is forced, so the jit cache holds at
most log2(X_max) entries as event counts fluctuate step to step and no
device program is sized by a step's own event count.

Optionally the static solve shards users across devices with ``shard_map``
(pass a ``repro.runtime.meshenv.MeshEnv``); each device runs the identical
batched Li-GD (fused or autodiff per ``cfg.solver``) on its slice of the
fleet — the solves are independent, so no collectives are needed.

Control-plane extensions on top of the paper's model (see
docs/ARCHITECTURE.md for the dataflow):

* **Admission control** — with ``candidates_k > 1`` (or a capacitated
  topology) the static plan solves Li-GD once per (user, candidate)
  pair — one fused launch over X·K rows, per-row edge params — and a
  deterministic water-filling greedy (``repro.core.admission``) admits
  each user to its cheapest candidate under the per-server compute /
  bandwidth budgets, spilling to the next candidate on saturation and
  falling back to device-only execution when every candidate is full.
  The per-server headroom lives in a persistent, delta-updated
  :class:`repro.core.ledger.BudgetLedger` shared by the static plan,
  handoff replanning, and fault evacuation.

* **Event pipeline** — :meth:`MCSAPlanner.on_events` is the incremental
  core: one step's handoffs + faults + capacity drains are normalized
  into a last-wins dirty set, solved by one fused candidate-set MLi-GD
  launch over the dirty rows only, admitted (argmin-U when
  uncapacitated; water-filling under the ledger's residuals otherwise,
  so handoff replanning is capacity-aware), and scattered sparsely.
  ``on_handoffs`` and ``on_faults`` are thin consumers of this
  pipeline.  A ``hysteresis`` margin keeps border users from
  ping-ponging: a user only switches servers when the re-split beats
  the stay/relay continuation by the margin.

* **Async replanning** — ``on_handoffs(..., sync=False)`` (or
  ``async_replanning=True`` at construction) dispatches the padded
  MLi-GD solve WITHOUT forcing it, so the next mobility step overlaps
  the solve (JAX async dispatch); the decisions are scattered into the
  fleet table up to ``async_horizon`` steps late — at later
  ``on_handoffs`` calls or an explicit :meth:`MCSAPlanner.drain`.
  ``sync=True`` preserves the original blocking semantics exactly.

This module is internal plumbing: the supported front door is
``repro.api`` (declarative :class:`~repro.api.Scenario`, the
:class:`~repro.api.Policy` protocol that :class:`MCSAPlanner`
implements, and the :class:`~repro.api.Session` stepped lifecycle).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from types import SimpleNamespace

from repro.telemetry.spans import span

from .admission import AdmissionReport, admit_waterfill
from .baselines import run_baseline_batch
from .costs import (Devices, LayerProfile, apply_congestion,
                    gather_devices, gather_devices_np, stack_devices,
                    stack_edges_np)
from .events import (DRAIN, EVACUATE, HANDOFF, DirtyBatch, DirtySet,
                     EventOutcome, StepEvents)
from .faults import EvacuationReport, FaultBatch, clamp_hops
from .ledger import BudgetLedger
from .ligd import LiGDConfig, LiGDResult, solve_ligd_batch, \
    solve_ligd_batch_jit
from .mligd import MLiGDResult, solve_mligd_batch_jit
from .mobility import HandoffBatch, HandoffEvent


@dataclasses.dataclass
class FleetState:
    """Array-resident plan table: one (X,) numpy array per planned
    quantity, row x = user x's current strategy.

    Columns
    -------
    server : int64   — serving edge server id (admission choice; for a
                       device-only fallback plan this is the nearest
                       candidate, kept for re-association)
    split  : int64   — split point s* ∈ [0, M]; s = M means device-only
                       (no offload, no rent)
    B      : float64 — allocated uplink bandwidth at the serving AP (Hz);
                       admission-control plans zero it at s = M (the
                       legacy K=1 path keeps the solver's last iterate
                       there — U/T/E/C never depend on it at s = M)
    r      : float64 — rented edge compute units; zeroed at s = M by
                       admission-control plans, like B
    U      : float64 — utility ω_T·T + ω_E·E + ω_C·CBR_C at the optimum
    T      : float64 — end-to-end inference delay (s)
    E      : float64 — device energy per inference (J)
    C      : float64 — renting cost per round ($)
    R      : int64   — last MLi-GD mobility decision (0 = re-split at the
                       new server, 1 = relay back to the original); 0
                       after a static plan
    """
    server: np.ndarray
    split: np.ndarray
    B: np.ndarray
    r: np.ndarray
    U: np.ndarray
    T: np.ndarray
    E: np.ndarray
    C: np.ndarray
    R: np.ndarray

    @classmethod
    def from_static(cls, servers: np.ndarray, res: LiGDResult
                    ) -> "FleetState":
        return cls(server=np.asarray(servers, np.int64),
                   split=np.asarray(res.split, np.int64),
                   B=np.asarray(res.B, np.float64),
                   r=np.asarray(res.r, np.float64),
                   U=np.asarray(res.U, np.float64),
                   T=np.asarray(res.T, np.float64),
                   E=np.asarray(res.E, np.float64),
                   C=np.asarray(res.C, np.float64),
                   R=np.zeros(len(np.atleast_1d(servers)), np.int64))

    def __len__(self) -> int:
        return len(self.server)

    def __getitem__(self, i: int) -> "UserPlan":
        # ndarray.item() yields a native int/float per the column dtype,
        # so new plan-table columns flow into the scalar view unchanged.
        return UserPlan(**{name: getattr(self, name)[i].item()
                           for name in PLAN_FIELDS})

    def scatter(self, users: np.ndarray, server: np.ndarray, res,
                R=None) -> None:
        """Write one result batch into rows ``users``: ``server`` from
        the argument (callers resolve relay-backs etc.), every other
        column from the same-named attribute of ``res`` (so new plan
        columns flow through automatically), ``R`` from the override
        when given (policies without a relay concept pass 0)."""
        self.server[users] = np.asarray(server, np.int64)
        for name in PLAN_FIELDS:
            if name == "server":
                continue
            col = getattr(self, name)
            val = R if name == "R" and R is not None \
                else getattr(res, name)
            col[users] = np.asarray(val, col.dtype)

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]


#: Plan-table column names, in declaration order — THE single source of
#: truth for what a plan row holds (UserPlan is generated from it).
PLAN_FIELDS = tuple(f.name for f in dataclasses.fields(FleetState))

# Scalar view of one user's plan (display/compat — the solve path never
# materializes these).  Generated from FleetState's own fields so a new
# plan-table column can never silently desync the two; every field
# defaults to 0 (matching the old ``R: int = 0``).
UserPlan = dataclasses.make_dataclass(
    "UserPlan",
    [(name, object, dataclasses.field(default=0)) for name in PLAN_FIELDS])
UserPlan.__doc__ = (
    "Scalar view of one user's plan — one native int/float per "
    "FleetState column (see FleetState docstring for field semantics). "
    "Generated from PLAN_FIELDS; display/compat only, the solve path "
    "never materializes these.")


def _pow2_bucket(n: int, floor: int = 8) -> int:
    """Smallest power of two >= max(n, floor) — bounds distinct jit shapes
    to log2(X_max) as per-step handoff counts fluctuate."""
    return max(floor, 1 << (n - 1).bit_length())


def _pad_rows(a: np.ndarray, pad: int) -> np.ndarray:
    """``a`` with its row 0 repeated ``pad`` times at the end (host-side
    padding to a pow2 bucket: padded rows solve a copy of row 0)."""
    return np.concatenate([a, np.repeat(a[:1], pad, axis=0)])


# Edge columns the frozen strategy's rent (Eq. 15) reads.
_RENT_FIELDS = ("rho_min", "rho_B", "B0", "gamma_B")


def _host_rows(res, n: int):
    """Force ``res`` and keep its first ``n`` rows, on the host (numpy
    slices, so no device program is sized by ``n``)."""
    return jax.tree.map(lambda a: a[:n], jax.device_get(res))


@dataclasses.dataclass
class _PendingReplan:
    """A dispatched-but-unapplied MLi-GD solve (async replanning).

    ``res`` leaves are un-forced jax arrays — the solve may still be in
    flight on the backend, at its padded bucket length; :meth:`force`
    pulls it to the host and keeps the first ``n`` rows when the replan
    is applied.  Up to ``MCSAPlanner.async_horizon`` of these can be
    outstanding at once; they apply FIFO, so a later dispatch's rows win
    per user."""
    res: MLiGDResult
    n: int                       # rows of ``res`` that are real (E)
    users: np.ndarray            # (E,) fleet rows the decisions scatter to
    orig_servers: np.ndarray     # (E,) pre-solve servers (relay-back target)
    new_server: object           # (E,) effective new server (jax or numpy)
    batch: Optional[object] = None   # the triggering DirtyBatch — kept
                                 # so a fault can retry stale rows
    attempts: int = 0            # fault-retry count for this dispatch
    stayed: int = 0              # hysteresis holds counted at apply time

    def force(self) -> MLiGDResult:
        """The result on the host with (E,) leaves (forces the solve;
        idempotent)."""
        self.res = _host_rows(self.res, self.n)
        return self.res


class MCSAPlanner:
    """MCSA control plane for one fleet (see the module docstring and
    docs/ARCHITECTURE.md).

    Parameters
    ----------
    profile       : the model's per-layer LayerProfile
    topo          : Topology (optionally capacitated)
    cfg           : LiGDConfig — solver backend + GD hyper-parameters
    per_iter_time : seconds per GD iteration, feeds the T_Ag CBR estimate
    candidates_k  : candidate-set size K for admission control; 1 (the
                    default) is the paper's one-server-per-AP model
    async_replanning : default ``sync`` polarity of :meth:`on_handoffs`
                    (False = today's blocking semantics)
    async_horizon : how many dispatched-but-unapplied replans may be
                    outstanding at once (async replanning); 1 (default)
                    is the classic one-step-stale drain, larger values
                    deepen the overlap window at the cost of staler
                    frozen originals
    hysteresis    : relative switch margin for handoff replanning — a
                    user only moves to a new server when the re-split
                    utility beats the stay/relay continuation by this
                    fraction (0 = always take the argmin, the paper's
                    behavior); with admission-aware handoff detection
                    this stops border users ping-ponging (one replan
                    per dwell, tested in tests/test_events.py)
    recovery_hold_steps : hysteresis — how many fault-preamble runs
                    a just-recovered server stays excluded from the
                    evacuation target set (users don't flap back the
                    instant it blips up)
    max_replan_retries : cap on re-dispatching one stale async replan
                    against the updated topology before its rows fall
                    through to the evacuation/degradation path
    """

    def __init__(self, profile: LayerProfile, topo,
                 cfg: LiGDConfig = LiGDConfig(),
                 per_iter_time: float = 5e-5,
                 candidates_k: int = 1,
                 async_replanning: bool = False,
                 async_horizon: int = 1,
                 hysteresis: float = 0.0,
                 recovery_hold_steps: int = 2,
                 max_replan_retries: int = 3):
        self.profile = profile
        self.topo = topo
        self.cfg = cfg
        self.per_iter_time = per_iter_time
        self.candidates_k = max(1, int(candidates_k))
        self.async_replanning = async_replanning
        self.async_horizon = max(1, int(async_horizon))
        self.hysteresis = float(hysteresis)
        self.recovery_hold_steps = int(recovery_hold_steps)
        self.max_replan_retries = int(max_replan_retries)
        self.t_ag_estimate = 0.0
        self.last_admission: Optional[AdmissionReport] = None
        self.last_evacuation: Optional[EvacuationReport] = None
        self.last_outcome: Optional[EventOutcome] = None
        self.replan_retries = 0      # stale async rows retried, cumulative
        self.ledger = BudgetLedger(topo)   # per-server budget residuals
        self.dirty = DirtySet()            # this step's event queue
        self._inflight: list = []          # FIFO _PendingReplan queue
        self._hold = np.zeros(topo.num_servers, np.int64)  # hysteresis
        self._last_user_aps: Optional[np.ndarray] = None
        # (Z, field) edge table — gathered per user by server id.
        self._edge_table = stack_edges_np(topo.edges)
        # observed-load view of the same table (repro.telemetry): stays
        # pointer-equal to _edge_table until update_load() sees a
        # non-identity LoadSnapshot — the feedback=off path never
        # diverges from the static pricing
        self._edge_table_eff = self._edge_table
        self.load = None                   # latest LoadSnapshot (or None)
        self._sharded_static = {}

    # ------------------------------------------------------------------
    def _edges_for(self, servers: np.ndarray) -> dict:
        """Per-user edge dict by gathering the per-topology table —
        O(fields), not O(users).  Reads the congestion-adjusted view,
        which IS the static table until feedback supplies a snapshot."""
        servers = np.asarray(servers)
        return {k: jnp.asarray(v[servers], jnp.float32)
                for k, v in self._edge_table_eff.items()}

    def _edge_rows(self, servers: np.ndarray,
                   fields: Optional[Sequence[str]] = None) -> dict:
        """:meth:`_edges_for` on the host: float32 numpy rows of the
        same view (only ``fields`` when given)."""
        table = self._edge_table_eff
        return {k: table[k][servers].astype(np.float32)
                for k in (fields or table)}

    def update_load(self, snapshot) -> None:
        """Consume a :class:`repro.telemetry.LoadSnapshot`: every
        subsequent dirty-set solve prices against the congestion-
        adjusted edge table (:func:`repro.core.costs.apply_congestion`)
        and ``_admit_dirty`` shrinks the waterfill residuals by the
        same multipliers — observed residual capacity, not rated.
        ``None`` (or an identity snapshot) restores static pricing
        exactly; ``feedback=off`` sessions never call this at all."""
        self.load = snapshot
        if snapshot is None:
            self._edge_table_eff = self._edge_table
            return
        self._edge_table_eff = apply_congestion(
            self._edge_table, snapshot.compute_mult,
            snapshot.backhaul_mult)
        if self._edge_table_eff is self._edge_table:
            self.load = None               # identity: pure static path

    def _stacked_devices(self, devices: Devices, hops: np.ndarray) -> dict:
        devs_s = dict(stack_devices(devices))
        X = len(hops)
        devs_s["hops"] = jnp.asarray(hops, jnp.float32)
        devs_s["t_ag"] = jnp.full((X,), self.t_ag_estimate, jnp.float32)
        return devs_s

    # ------------------------------------------------------------------
    def plan(self, devices: Devices, user_aps: np.ndarray,
             env=None) -> FleetState:
        """The ``repro.api.Policy`` entry point: plan every user and
        return the scattered :class:`FleetState` (use :meth:`plan_static`
        when you also need the raw batched LiGDResult / server ids)."""
        return self.plan_static(devices, user_aps, env=env)[2]

    def plan_static(self, devices: Devices, user_aps: np.ndarray,
                    env=None, candidates_k: Optional[int] = None) -> tuple:
        """Plan every user in one vectorized call.

        Arguments
        ---------
        devices  : DeviceFleet (or sequence of DeviceParams), X users
        user_aps : (X,) int — each user's associated AP
        env      : optional MeshEnv — when SPMD and the solve batch
                   divides the data-parallel size, users are sharded
                   across devices with shard_map (independent solves, no
                   collectives)
        candidates_k : per-call override of the planner's candidate-set
                   size K

        Returns ``(res, servers, fleet)``: a batched LiGDResult with (X,)
        leaves (per-layer fields are (X, M+1)), the (X,) admitted server
        ids, and the scattered :class:`FleetState`.

        With K = 1 on an uncapacitated topology this is the paper's
        one-server-per-AP plan.  Otherwise Li-GD is solved once per
        (user, candidate) — a single fused launch over X·K rows — and the
        water-filling greedy of ``repro.core.admission`` assigns servers
        under the per-server budgets; the outcome is stored in
        ``self.last_admission``.  Any in-flight async replan is dropped
        (a fresh static plan supersedes it), and the budget ledger is
        re-derived from the new plan table.
        """
        self._inflight.clear()
        K = self.candidates_k if candidates_k is None else max(
            1, int(candidates_k))
        K = min(K, self.topo.num_servers)
        user_aps = np.asarray(user_aps)
        self._last_user_aps = user_aps
        # a faulted topology always takes the candidate path: it masks
        # down/unreachable servers and owns the device-only degrade
        if K == 1 and not self.topo.capacitated and not self.topo.faulted:
            self.last_admission = None
            servers = self.topo.ap_server[user_aps]
            hops = self.topo.hops[user_aps, servers]
            devs_s = self._stacked_devices(devices, hops)
            edges_s = self._edges_for(servers)
            res = self._solve_static(devs_s, edges_s, env)
            jax.block_until_ready(res.U)
            self._update_t_ag(res)
            fleet = FleetState.from_static(servers, res)
            self.ledger.reset_from_fleet(fleet, self.profile.num_layers)
            return res, servers, fleet
        return self._plan_admission(devices, user_aps, K, env)

    def _update_t_ag(self, res: LiGDResult) -> None:
        # Eq. 6/7 feedback: observed per-user strategy time for future CBR.
        iters = float(np.mean(np.sum(np.asarray(res.iters_per_layer), -1)))
        self.t_ag_estimate = iters * self.per_iter_time

    def _plan_admission(self, devices: Devices, user_aps: np.ndarray,
                        K: int, env) -> tuple:
        """Candidate-set static plan: one Li-GD solve per (user, candidate)
        row — user-major tiling, row x·K+k is user x's k-th candidate —
        then water-filling admission under the per-server budgets."""
        topo = self.topo
        X = len(user_aps)
        cand = topo.candidates(K)[user_aps]                     # (X, K)
        K = cand.shape[1]
        hops = topo.hops[user_aps[:, None], cand]               # (X, K)
        reachable = None
        if topo.faulted:
            # mask candidates that are down or unreachable: invalid
            # slots are filled with the row's first valid candidate (a
            # duplicate proposal is an admission no-op), rows with no
            # valid candidate are forced device-only after admission
            up = topo.server_available()
            valid = up[cand] & np.isfinite(np.asarray(hops, np.float64))
            reachable = valid.any(axis=1)
            rows_i = np.arange(X)
            first = np.argmax(valid, axis=1)
            cand = np.where(valid, cand, cand[rows_i, first][:, None])
            hops = np.where(valid, hops, hops[rows_i, first][:, None])
            hops = clamp_hops(hops)
        t_ag_used = self.t_ag_estimate
        dev_rows = gather_devices(devices, np.repeat(np.arange(X), K))
        dev_rows["hops"] = jnp.asarray(hops.reshape(-1), jnp.float32)
        dev_rows["t_ag"] = jnp.full((X * K,), t_ag_used, jnp.float32)
        edge_rows = self._edges_for(cand.reshape(-1))
        res = self._solve_static(dev_rows, edge_rows, env)
        jax.block_until_ready(res.U)
        self._update_t_ag(res)

        # a candidate whose solved optimum is device-only (s = M) rents
        # nothing — its demand on the server is zero, whatever (B, r)
        # values the GD iterate happened to stop at
        offl = (np.asarray(res.split).reshape(X, K)
                < self.profile.num_layers)
        report = admit_waterfill(
            cand, np.asarray(res.U, np.float64).reshape(X, K),
            np.asarray(res.r, np.float64).reshape(X, K) * offl,
            np.asarray(res.B, np.float64).reshape(X, K) * offl,
            topo.num_servers, topo.r_capacity, topo.B_capacity)
        if reachable is not None and not reachable.all():
            # no up server in reach of these users' APs: force the
            # device-only fallback and keep the association off the
            # dead server (nearest up server, for later re-admission)
            report.rejected = report.rejected | ~reachable
            choice = report.choice.copy()
            choice[~reachable] = -1
            report.choice = choice
            srv = report.server.copy()
            srv[~reachable] = self._nearest_up(
                user_aps[~reachable], topo.server_available())
            report.server = srv
        self.last_admission = report

        # gather each user's admitted row out of the (X*K,) solve
        flat = np.arange(X) * K + np.where(report.rejected, 0, report.choice)
        res_sel = jax.tree.map(lambda a: np.asarray(a)[flat], res)
        dev_only = np.asarray(res_sel.split) >= self.profile.num_layers
        if dev_only.any():
            # keep the plan table honest: device-only rows hold no
            # resources (U/T/E/C are already offload-free at s = M)
            B = np.array(res_sel.B)
            r = np.array(res_sel.r)
            B[dev_only] = 0.0
            r[dev_only] = 0.0
            res_sel = res_sel._replace(B=B, r=r)
        if report.rejected.any():
            res_sel = self._device_only_fallback(
                res_sel, devices, report.rejected, t_ag_used)
        fleet = FleetState.from_static(report.server, res_sel)
        self.ledger.reset_from_fleet(fleet, self.profile.num_layers)
        return res_sel, report.server, fleet

    def _device_only_plan(self, devices: Devices, idx: np.ndarray,
                          t_ag: float) -> tuple:
        """(T, E, U) of the device-only plan (s = M) for fleet rows
        ``idx`` — nothing offloaded: no bandwidth, no rent, no admission
        load (shared by the rejection fallback and fault degradation)."""
        d = {k: np.asarray(v, np.float64)
             for k, v in gather_devices_np(devices, idx).items()}
        f_l_M = float(self.profile.prefix_tables()[0][-1])
        T = f_l_M / d["c_dev"] + t_ag / d["k_rounds"]
        E = d["xi"] * d["c_dev"] ** 2 * d["phi"] * f_l_M
        U = d["w_T"] * T + d["w_E"] * E
        return T, E, U

    def _device_only_fallback(self, res, devices: Devices,
                              rejected: np.ndarray, t_ag: float,
                              rows: Optional[np.ndarray] = None):
        """Overwrite rejected users' rows with the device-only plan
        (s = M): nothing is offloaded, so no bandwidth/compute is rented
        and the admission budgets are untouched.  ``rows`` maps result
        rows to fleet/device rows when ``res`` covers a subset (the
        evacuation path); None means result row i is device row i.
        Works for both LiGDResult and MLiGDResult batches (the latter
        additionally zeroes the relay decision R)."""
        idx = np.nonzero(rejected)[0]
        dev_idx = idx if rows is None else np.asarray(rows)[idx]
        T, E, U = self._device_only_plan(devices, dev_idx, t_ag)
        out = {f: np.array(getattr(res, f)) for f in res._fields}
        out["split"][idx] = self.profile.num_layers
        out["B"][idx] = 0.0
        out["r"][idx] = 0.0
        out["U"][idx] = U
        out["T"][idx] = T
        out["E"][idx] = E
        out["C"][idx] = 0.0
        if "R" in out:
            out["R"][idx] = 0
        return type(res)(**out)

    def _solve_static(self, devs_s, edges_s, env) -> LiGDResult:
        X = devs_s["c_dev"].shape[0]
        if env is not None and env.is_spmd and env.dp > 1 \
                and X % env.dp == 0:
            return self._solve_static_sharded(devs_s, edges_s, env)
        return solve_ligd_batch_jit(self.profile, devs_s, edges_s, self.cfg)

    def _solve_static_sharded(self, devs_s, edges_s, env) -> LiGDResult:
        """Data-parallel Li-GD: users sharded over the mesh batch axes."""
        from repro.runtime.meshenv import shard_map
        key = (self.profile.fingerprint, self.cfg, env.mesh, env.batch())
        fn = self._sharded_static.get(key)
        if fn is None:
            spec = P(env.batch())
            profile, cfg = self.profile, self.cfg

            def solve(d, e):
                return solve_ligd_batch(profile, d, e, cfg)

            fn = jax.jit(shard_map(solve, mesh=env.mesh,
                                   in_specs=(spec, spec), out_specs=spec))
            self._sharded_static[key] = fn
        return fn(devs_s, edges_s)

    # ------------------------------------------------------------------
    # The incremental event pipeline (docs/ARCHITECTURE.md,
    # "Event lifecycle"): handoffs, fault evacuations, and capacity
    # drains all flow through ONE dirty-set solve per step.
    # ------------------------------------------------------------------
    def on_events(self, events, devices: Devices, fleet: FleetState,
                  user_aps: Optional[np.ndarray] = None,
                  sync: Optional[bool] = None,
                  _attempts: int = 0) -> EventOutcome:
        """Replan everything one step dirtied, in one fused solve.

        ``events`` is a :class:`repro.core.events.StepEvents` (mobility
        handoffs + optionally the step's applied FaultBatch); a bare
        HandoffBatch / event sequence is accepted for convenience.
        Returns an :class:`~repro.core.events.EventOutcome`; the plan
        table is updated in place (or marked in-flight under async
        replanning).

        Pipeline: (1) the fault preamble (only when ``events.faults`` is
        not None) decays the recovery hold, retries stale async rows,
        re-associates device-only users, and enqueues EVACUATE rows for
        users on down/unreachable servers plus DRAIN rows for servers
        whose effective capacity shrank below their ledger usage;
        (2) the handoff batch enqueues HANDOFF rows; (3) the dirty set
        flushes with last-wins dedup (a user both evacuated and handed
        off in one tick is solved ONCE, against its freshest AP);
        (4) one padded MLi-GD solve over the dirty rows; (5) admission —
        the classic argmin-U reduction on uncapacitated pure-handoff
        steps (bit-for-bit the historical path), or the water-filling
        greedy under the :class:`~repro.core.ledger.BudgetLedger`
        residuals when the topology is capacitated or fault rows are
        present; (6) sparse scatter (sync) or a pending dispatch
        (async).  Fault-bearing calls always run synchronously — an
        evacuation must land within its step."""
        if not isinstance(events, StepEvents):
            events = StepEvents.from_handoffs(events)
        if sync is None:
            sync = not self.async_replanning
        t = float(events.t)
        pre = None
        if events.faults is not None:
            sync = True               # evacuations must land this step
            with span("planner.fault_preamble"):
                pre = self._fault_preamble(events.faults, devices, fleet,
                                           user_aps)
        else:
            # bring the table within the async horizon before freezing
            # originals (the default horizon 1 applies everything —
            # exactly the historical one-step-stale behavior)
            keep = self.async_horizon - 1
            with span("planner.apply", rows=self._inflight_rows(keep)):
                self._apply_inflight(fleet, keep=keep)
        with span("planner.flush"):
            self.dirty.enqueue_handoffs(events.handoffs)
            dirty = self.dirty.flush()
            n_hand = dirty.count(HANDOFF)

        if len(dirty) == 0:
            outcome = EventOutcome(t=t, result=None, dirty=dirty,
                                   relays=0, resplits=0, stays=0)
        else:
            use_admission = self.topo.capacitated or \
                bool((dirty.kind != HANDOFF).any())
            sol = self._solve_dirty(dirty, devices, fleet,
                                    reduce=not use_admission)
            if use_admission:
                with span("planner.admit"):
                    result, relays, stays, admission = self._admit_dirty(
                        dirty, devices, fleet, sol)
                outcome = EventOutcome(
                    t=t, result=result, dirty=dirty, relays=relays,
                    resplits=n_hand - relays, stays=stays)
                if pre is not None:
                    pre.admission = admission
            else:
                p = _PendingReplan(res=sol.res, n=sol.n, users=dirty.user,
                                   orig_servers=sol.orig_servers,
                                   new_server=sol.new_server,
                                   batch=dirty, attempts=_attempts)
                self._inflight.append(p)
                if sync:
                    with span("planner.scatter"):
                        res = self._apply_inflight(fleet, keep=0)
                        relays = int(np.asarray(res.R, bool).sum()) \
                            + p.stayed
                    outcome = EventOutcome(
                        t=t, result=res, dirty=dirty, relays=relays,
                        resplits=n_hand - relays, stays=p.stayed)
                else:
                    outcome = EventOutcome(t=t, result=p.res, dirty=dirty,
                                           in_flight=True)

        if pre is not None:
            outcome.evacuation = self._evacuation_report(pre, fleet, t)
        self.last_outcome = outcome
        return outcome

    def _fault_preamble(self, batch: FaultBatch, devices: Devices,
                        fleet: FleetState,
                        user_aps: Optional[np.ndarray]) -> SimpleNamespace:
        """Fault bookkeeping + dirty-set producers (no solve here): hold
        decay, stale-pending retry, device-only re-association, EVACUATE
        rows for users offloading to down/unreachable servers, DRAIN
        rows for capacity-churn overflow."""
        topo = self.topo
        up = topo.server_available()
        t = float(getattr(batch, "t", 0.0))

        self._hold = np.maximum(self._hold - 1, 0)
        if len(batch.server_up):
            self._hold[np.asarray(batch.server_up, np.int64)] = \
                self.recovery_hold_steps

        retried = self._retry_stale_pending(devices, fleet, up)
        pre = SimpleNamespace(retried=retried, reassociated=0,
                              evac_idx=np.zeros(0, np.int64), drained=0,
                              admission=None)
        if user_aps is None:
            user_aps = self._last_user_aps
        if user_aps is None:          # never planned: nothing to evacuate
            return pre
        user_aps = np.asarray(user_aps)

        offl = fleet.split < self.profile.num_layers
        on_down = ~up[fleet.server]
        unreachable = offl & ~np.isfinite(np.asarray(
            topo.hops[user_aps, fleet.server], np.float64))
        affected = (on_down & offl) | unreachable
        assoc_only = on_down & ~offl

        if assoc_only.any() and up.any():
            fleet.server[assoc_only] = self._nearest_up(
                user_aps[assoc_only], up)
            pre.reassociated = int(assoc_only.sum())

        pre.evac_idx = np.nonzero(affected)[0]
        if len(pre.evac_idx):
            aps_e = user_aps[pre.evac_idx]
            tgt = self._nearest_up(aps_e, up) if up.any() \
                else fleet.server[pre.evac_idx]
            self.dirty.enqueue_evacuations(
                pre.evac_idx, fleet.server[pre.evac_idx], tgt, aps_e,
                clamp_hops(topo.hops[aps_e, tgt]).astype(np.int64), t=t)

        if topo.capacitated:
            pre.drained = self._enqueue_drains(fleet, user_aps, affected,
                                               up, t)
        return pre

    def _enqueue_drains(self, fleet: FleetState, user_aps: np.ndarray,
                        affected: np.ndarray, up: np.ndarray,
                        t: float) -> int:
        """Capacity churn: servers whose LIVE effective capacity dropped
        below their ledger usage shed their most expensive plans back
        into the dirty set (per server, users are ranked by utility and
        the cheapest prefix that still fits is kept).  The drained rows
        re-admit through the same waterfill — possibly back onto their
        origin if the freed headroom suffices."""
        topo = self.topo
        over = self.ledger.overloaded() & up
        if not over.any():
            return 0
        M = self.profile.num_layers
        r_cap = None if topo.r_capacity is None \
            else np.asarray(topo.r_capacity, np.float64)
        B_cap = None if topo.B_capacity is None \
            else np.asarray(topo.B_capacity, np.float64)
        offl = fleet.split < M
        drop_rows = []
        for z in np.nonzero(over)[0]:
            rows = np.nonzero(offl & (fleet.server == z) & ~affected)[0]
            if len(rows) == 0:
                continue
            order = rows[np.argsort(fleet.U[rows], kind="stable")]
            keep = np.ones(len(order), bool)
            if r_cap is not None:
                keep &= np.cumsum(fleet.r[order]) <= r_cap[z] + 1e-9
            if B_cap is not None:
                keep &= np.cumsum(fleet.B[order]) <= B_cap[z] + 1e-9
            if not keep.all():
                drop_rows.append(order[~keep])
        if not drop_rows:
            return 0
        idx = np.concatenate(drop_rows)
        aps_d = np.asarray(user_aps)[idx]
        tgt = self._nearest_up(aps_d, up)
        self.dirty.enqueue_evacuations(
            idx, fleet.server[idx], tgt, aps_d,
            clamp_hops(self.topo.hops[aps_d, tgt]).astype(np.int64),
            t=t, kind=DRAIN)
        return len(idx)

    def _evacuation_report(self, pre: SimpleNamespace, fleet: FleetState,
                           t: float) -> EvacuationReport:
        """Post-scatter accounting over the evacuated rows: re-admitted
        to a live server = evacuated, device-only = degraded (the two
        partition ``users`` exactly — rows superseded by a same-tick
        handoff entry were still replanned off the dead server)."""
        evac_idx = pre.evac_idx
        evacuated = degraded = 0
        if len(evac_idx):
            up = self.topo.server_available()
            offl = fleet.split[evac_idx] < self.profile.num_layers
            evacuated = int((offl & up[fleet.server[evac_idx]]).sum())
            degraded = len(evac_idx) - evacuated
        rep = EvacuationReport(t=t, users=evac_idx, evacuated=evacuated,
                               degraded=degraded,
                               reassociated=pre.reassociated,
                               retried=pre.retried, drained=pre.drained,
                               admission=pre.admission)
        self.last_evacuation = rep
        return rep

    def _solve_dirty(self, dirty: DirtyBatch, devices: Devices,
                     fleet: FleetState, reduce: bool) -> SimpleNamespace:
        """ONE padded, jitted MLi-GD solve over the dirty rows (all
        kinds).  With ``candidates_k > 1`` each row is solved per
        candidate-of-its-AP (D·K rows); EVACUATE/DRAIN rows carry
        ``hops_back = HOP_UNREACHABLE`` so the relay-back vertex never
        wins, and their candidates additionally exclude held
        (just-recovered) servers unless nothing else survives.

        ``reduce=True`` (the uncapacitated pure-handoff path) applies
        the classic argmin-U candidate reduction on the un-forced jax
        arrays — bit-for-bit the historical ``on_handoffs`` solve;
        ``reduce=False`` returns the full (D·K,) result for the
        ledger-aware waterfill admission."""
        n = len(dirty)
        users = dirty.user
        K = min(self.candidates_k, self.topo.num_servers)
        pad = _pow2_bucket(n * K) - n * K
        with span("planner.solve_prep", rows=n * K, bucket=n * K + pad):
            faulted = self.topo.faulted
            up = self.topo.server_available() if faulted else None
            evacish = dirty.kind != HANDOFF

            cand = None
            cand_invalid = None
            if K > 1:
                cand = self.topo.candidates(K)[dirty.new_ap]         # (n, K)
                hops_new = self.topo.hops[dirty.new_ap[:, None], cand]
                if faulted:
                    # down/unreachable candidates stay in the solve (static
                    # shapes) but are priced out of the selection below
                    cand_invalid = ~up[cand] | ~np.isfinite(
                        np.asarray(hops_new, np.float64))
                    hops_new = clamp_hops(hops_new)
                if evacish.any() and (self._hold > 0).any():
                    # recovery hysteresis: evacuees avoid just-recovered
                    # servers unless one is their only surviving candidate
                    held = self._hold > 0
                    base = cand_invalid if cand_invalid is not None \
                        else np.zeros(cand.shape, bool)
                    strict = base | held[cand]
                    use_strict = evacish & (~strict).any(axis=1)
                    if use_strict.any():
                        cand_invalid = np.where(use_strict[:, None],
                                                strict, base)
                rows = np.repeat(np.arange(n), K)
                new_server_rows = cand.reshape(-1)
                hops_new_rows = hops_new.reshape(-1)
            else:
                rows = np.arange(n)
                new_server_rows = dirty.new_server
                hops_new_rows = dirty.hops_new
                if faulted:
                    # the nearest-coverage target may be down (ap_server
                    # falls back to the pre-fault map where nothing is
                    # reachable): retarget those events to the nearest up
                    # server so a handoff can never land on a dead one
                    tgt = np.asarray(new_server_rows, np.int64).copy()
                    dead = ~up[tgt]
                    if dead.any() and up.any():
                        tgt[dead] = self._nearest_up(dirty.new_ap[dead], up)
                        new_server_rows = tgt
                    hops_new_rows = clamp_hops(
                        self.topo.hops[dirty.new_ap, new_server_rows])

            # every input at bucket length on the host: the index columns
            # are padded by repeating row 0 and each leaf gathered through
            # them, so the padded rows are copies of row 0
            dev_rows = _pad_rows(users[rows], pad)
            srv_new = _pad_rows(new_server_rows, pad)
            dev_b = gather_devices_np(devices, dev_rows)
            dev_b["hops"] = _pad_rows(hops_new_rows, pad).astype(np.float32)
            dev_b["t_ag"] = np.full(n * K + pad, self.t_ag_estimate,
                                    np.float32)
            edges_new = self._edge_rows(srv_new)

            # Frozen original strategies, gathered straight from fleet arrays
            # (the batched equivalent of mligd.orig_strategy_dict).
            f_l_np, f_e_np, w_np = self.profile.prefix_tables()
            s = fleet.split[dev_rows]
            # device-only plans carry r = 0: their rent must price the true
            # r (zero — nothing rented), but U₂'s f_e_o/(λ(r_o)·c_min) term
            # would hit 0/0 (f_e = 0 at s = M), so λ sees a unit stand-in
            # that the zero f_e multiplies away; the rent itself is priced
            # inside the jitted solve (mligd.with_rent)
            r_raw = fleet.r[dev_rows]
            orig_servers = fleet.server[users]
            origs = {
                "split": s.astype(np.int32),
                "f_l": f_l_np[s].astype(np.float32),
                "f_e": f_e_np[s].astype(np.float32),
                "w": w_np[s].astype(np.float32),
                "r": np.where(r_raw > 0, r_raw, 1.0).astype(np.float32),
                "B": fleet.B[dev_rows].astype(np.float32),
                "r_true": r_raw.astype(np.float32),
            }
            edges_orig = self._edge_rows(fleet.server[dev_rows], _RENT_FIELDS)
            hops_back_np = dirty.hops_back[rows]
            if faulted:
                # a relay-back to a dead original server must price as
                # unreachable, never as a wrapped/NaN path (EVACUATE/DRAIN
                # rows arrive pre-clamped at HOP_UNREACHABLE)
                hops_back_np = clamp_hops(hops_back_np)
            hops_back = _pad_rows(hops_back_np, pad).astype(np.float32)
            # one transfer of the whole batch
            args = jax.device_put((dev_b, edges_new, origs, hops_back,
                                   edges_orig))
        with span("planner.dispatch"):
            res = solve_mligd_batch_jit(self.profile, *args[:4], self.cfg,
                                        edge_orig=args[4])

            new_server = None
            if reduce:
                if K > 1:
                    # argmin-U candidate per event (jnp, so the reduction
                    # rides the async dispatch — nothing is forced here)
                    res = jax.tree.map(lambda a: a[:n * K], res)
                    U_eff = res.U.reshape(n, K)
                    if cand_invalid is not None and cand_invalid.any():
                        U_eff = U_eff + jnp.where(jnp.asarray(cand_invalid),
                                                  jnp.inf, 0.0)
                    best_k = jnp.argmin(U_eff, axis=1)
                    take = lambda a: a.reshape(n, K, *a.shape[1:])[
                        jnp.arange(n), best_k]
                    res = jax.tree.map(take, res)
                    new_server = jnp.take_along_axis(
                        jnp.asarray(cand), best_k[:, None], axis=1)[:, 0]
                else:
                    new_server = np.asarray(new_server_rows, np.int64)

        # ``res`` stays bucket-long unless the K>1 reduction cut it; ``n``
        # is how many of its rows are real
        return SimpleNamespace(res=res, K=K, n=n if reduce else n * K,
                               cand=cand,
                               cand_invalid=cand_invalid,
                               new_server_rows=new_server_rows,
                               new_server=new_server,
                               orig_servers=orig_servers)

    def _reprice_T_physical(self, res_sel, devices: Devices,
                            rows: np.ndarray, servers: np.ndarray,
                            hops: np.ndarray, t_ag: float):
        """Recompute the selected rows' per-round delay T against the
        PHYSICAL (uncongested) edge table — Eqs. (1)/(3)/(5)/(7) at the
        already-chosen (split, B, r, server).  Only called while a
        LoadSnapshot is active: the congestion-adjusted table steers
        which plan wins, but the scattered T must stay a service-time
        estimate, because the serving layer derives its virtual
        per-token time from it and models queueing explicitly."""
        M = self.profile.num_layers
        f_l, f_e, w = self.profile.prefix_tables()
        split = np.asarray(res_sel.split, np.int64)
        offl = split < M
        et = self._edge_table
        z = np.asarray(servers, np.int64)
        dv = gather_devices(devices, np.asarray(rows))
        c_dev = np.asarray(dv["c_dev"], np.float64)
        k_rounds = np.asarray(dv["k_rounds"], np.float64)
        B = np.maximum(np.asarray(res_sel.B, np.float64), 1.0)
        r = np.maximum(np.asarray(res_sel.r, np.float64), 1e-9)
        h = np.asarray(clamp_hops(np.asarray(hops, np.float64)))
        h = np.where(np.isfinite(h), h, 1.0)
        payload = w[split] + float(self.profile.result_bits)
        t_dev = f_l[split] / c_dev + float(t_ag) / k_rounds
        t_srv = f_e[split] / (np.power(r, et["lam_a"][z])
                              * et["c_min"][z])
        t_tx = payload / B + h * payload / et["B_backhaul"][z]
        T = t_dev + np.where(offl, t_srv + t_tx, 0.0)
        return res_sel._replace(T=T)

    def _admit_dirty(self, dirty: DirtyBatch, devices: Devices,
                     fleet: FleetState, sol: SimpleNamespace) -> tuple:
        """Ledger-aware admission over the dirty solve: release what the
        replanned rows held, water-fill the per-(row, candidate) plans
        under the residual budgets (relay-back columns re-admit to the
        original server), degrade rejected rows to device-only, scatter,
        and charge the new holdings back to the ledger.  Returns
        ``(result, relays, stays, AdmissionReport-or-None)``."""
        topo = self.topo
        M = self.profile.num_layers
        n = len(dirty)
        users = dirty.user
        up = topo.server_available()
        t_ag = self.t_ag_estimate
        res_np = _host_rows(sol.res, sol.n)           # forces the solve

        if sol.cand is not None:
            cand = sol.cand
        else:
            cand = np.asarray(sol.new_server_rows, np.int64).reshape(n, 1)
        Kc = cand.shape[1]
        invalid = sol.cand_invalid
        if invalid is None:
            invalid = np.zeros((n, Kc), bool)
            if topo.faulted or not up.all():
                invalid |= ~up[cand]
        old_server = np.asarray(fleet.server[users], np.int64)

        split_m = np.asarray(res_np.split).reshape(n, Kc)
        offl_m = split_m < M
        Uv = np.asarray(res_np.U, np.float64).reshape(n, Kc)
        R_mat = np.asarray(res_np.R, bool).reshape(n, Kc)
        r_dem = np.asarray(res_np.r, np.float64).reshape(n, Kc) * offl_m
        B_dem = np.asarray(res_np.B, np.float64).reshape(n, Kc) * offl_m

        handoff = np.asarray(dirty.kind == HANDOFF)
        # switch hysteresis: a handoff-row user keeps its current plan
        # row untouched unless the best re-split beats the stay/relay
        # continuation by the margin (EVACUATE/DRAIN rows always move)
        stay = np.zeros(n, bool)
        if self.hysteresis > 0.0 and handoff.any():
            u1b = np.where(invalid, np.inf,
                           np.asarray(res_np.U_recalc,
                                      np.float64).reshape(n, Kc)).min(1)
            u2b = np.where(invalid, np.inf,
                           np.asarray(res_np.U_back,
                                      np.float64).reshape(n, Kc)).min(1)
            stay = handoff & up[old_server] \
                & (u2b <= u1b * (1.0 + self.hysteresis))
        stays = int(stay.sum())
        sel = np.nonzero(~stay)[0]
        if len(sel) == 0:
            return None, stays, stays, None

        # the replanned rows' current holdings come off the ledger
        # first — the waterfill must see their headroom as free (the
        # evacuation half of this is exactly what the old
        # ``_residual_budgets`` fleet sweep recomputed per call)
        self.ledger.release_rows(fleet, users[sel], M)

        cand_s = cand[sel]
        invalid_s = invalid[sel]
        # a relay-back column re-admits to the ORIGINAL server with the
        # relay demands (orig r, B_back — charged where the live-load
        # accounting charges them)
        serv_s = np.where(R_mat[sel], old_server[sel][:, None], cand_s)
        U_s = Uv[sel].copy()
        r_s = r_dem[sel]
        B_s = B_dem[sel]
        has_valid = (~invalid_s).any(axis=1)
        if invalid_s.any():
            # invalid columns become +inf-priced duplicates of the row's
            # first valid column (a duplicate proposal is an admission
            # no-op); all-invalid rows bypass admission entirely
            ri = np.arange(len(sel))
            first = np.where(has_valid, np.argmax(~invalid_s, axis=1), 0)
            serv_s = np.where(invalid_s, serv_s[ri, first][:, None],
                              serv_s)
            r_s = np.where(invalid_s, r_s[ri, first][:, None], r_s)
            B_s = np.where(invalid_s, B_s[ri, first][:, None], B_s)
            U_s[invalid_s] = np.inf

        res_r = self.ledger.residual_r()
        res_B = self.ledger.residual_B()
        if self.load is not None:
            # observed residual capacity: a congested server's headroom
            # shrinks by the same multiplier that slowed its pricing,
            # so the waterfill spills load to quiet servers even when
            # the rated budgets say there is room
            if res_r is not None:
                res_r = res_r / np.maximum(self.load.compute_mult, 1.0)
            if res_B is not None:
                res_B = res_B / np.maximum(self.load.backhaul_mult, 1.0)
        report = admit_waterfill(serv_s, U_s, r_s, B_s, topo.num_servers,
                                 res_r, res_B)
        if not has_valid.all():
            report.rejected = report.rejected | ~has_valid
            choice = report.choice.copy()
            choice[~has_valid] = -1
            report.choice = choice

        gflat = sel * Kc + np.where(report.rejected, 0,
                                    np.maximum(report.choice, 0))
        res_sel = jax.tree.map(lambda a: a[gflat], res_np)
        dev_only = np.asarray(res_sel.split) >= M
        if dev_only.any():
            B = np.array(res_sel.B)
            r = np.array(res_sel.r)
            B[dev_only] = 0.0
            r[dev_only] = 0.0
            res_sel = res_sel._replace(B=B, r=r)
        if report.rejected.any():
            res_sel = self._device_only_fallback(
                res_sel, devices, report.rejected, t_ag, rows=users[sel])

        final_srv = np.asarray(report.server, np.int64).copy()
        if not has_valid.all():
            nv = ~has_valid
            # nothing reachable: keep the association useful — nearest
            # up server, or the frozen one during a full blackout
            final_srv[nv] = self._nearest_up(dirty.new_ap[sel][nv], up) \
                if up.any() else old_server[sel][nv]
        if self.load is not None:
            # feedback prices the DECISION against observed congestion,
            # but the table's T column is what the data plane turns
            # into virtual token time — leaving it inflated would
            # double-count queueing the engine pools already simulate
            res_sel = self._reprice_T_physical(
                res_sel, devices, users[sel], final_srv,
                self.topo.hops[dirty.new_ap[sel], final_srv], t_ag)
        fleet.scatter(users[sel], final_srv, res_sel)

        offl_new = np.asarray(res_sel.split) < M
        self.ledger.charge(final_srv[offl_new],
                           np.asarray(res_sel.r)[offl_new],
                           np.asarray(res_sel.B)[offl_new])

        hand_sel = handoff[sel]
        relays = stays + int(np.asarray(res_sel.R,
                                        np.int64)[hand_sel].sum())
        return res_sel, relays, stays, report

    # ------------------------------------------------------------------
    def on_handoffs(self, events: Union[HandoffBatch,
                                        Sequence[HandoffEvent]],
                    devices: Devices, fleet: FleetState,
                    sync: Optional[bool] = None,
                    _attempts: int = 0
                    ) -> Optional[MLiGDResult]:
        """One padded, jitted MLi-GD solve over ALL of this step's handoff
        events — a thin consumer of :meth:`on_events` (HANDOFF rows
        only).  Returns the (unpadded) batched MLiGDResult with (E,)
        leaves, or None when there are no events; a replan left in
        flight (``sync=False``) returns its un-forced result at the
        padded bucket length instead, whose first E rows are the events
        (cutting it on the device would build a program per event
        count).

        Arguments
        ---------
        events  : HandoffBatch (or sequence of HandoffEvent views), E
                  events; ``user`` indexes rows of ``fleet``
        devices : the SAME fleet ``plan_static`` planned (row-aligned)
        fleet   : FleetState to scatter decisions into
        sync    : None (default) follows the planner's
                  ``async_replanning`` flag; True blocks and scatters
                  before returning (the original semantics); False
                  dispatches the solve and defers the scatter to a later
                  ``on_handoffs``/:meth:`drain` call, so the caller's
                  next mobility steps overlap the solve (up to
                  ``async_horizon`` steps of staleness)

        With ``candidates_k > 1`` the re-solve is evaluated per (event,
        candidate-of-the-new-AP) — E·K rows through the same padded
        solve.  On an uncapacitated topology the argmin-utility
        candidate wins (ties toward the nearer candidate); on a
        capacitated one the rows are water-filled under the budget
        ledger's residuals — handoff replanning is capacity-aware, and
        a saturated candidate spills to the next one exactly like the
        static plan (docs/ARCHITECTURE.md, "Event lifecycle").

        Duplicate users within a batch (only possible when batches are
        concatenated across steps): every event's frozen original strategy
        is read from the PRE-CALL fleet state — exactly like the seed
        loop, which built all origs before applying any update — and the
        last event's decision wins per field.  A relay-back therefore
        restores the pre-call server (the one its frozen strategy was
        priced against), which is self-consistent where the seed's
        sequential server bookkeeping could disagree with the orig it had
        just solved with."""
        outcome = self.on_events(events, devices, fleet, sync=sync,
                                 _attempts=_attempts)
        return outcome.result

    @property
    def pending(self) -> bool:
        """True while an async replan is dispatched but not yet applied
        to the fleet table — the ``repro.api.Policy`` in-flight signal
        (``repro.api.Session`` reads it to avoid forcing the solve)."""
        return len(self._inflight) > 0

    @property
    def _pending(self) -> Optional[_PendingReplan]:
        """The newest in-flight replan (None when the table is up to
        date) — kept as a read-only view now that the planner holds a
        FIFO of up to ``async_horizon`` dispatches."""
        return self._inflight[-1] if self._inflight else None

    def drain(self, fleet: FleetState) -> Optional[MLiGDResult]:
        """Force and scatter ALL in-flight async replans, if any.  Call
        once after the mobility loop (or before reading ``fleet`` between
        steps) to bring the plan table fully up to date.  Returns the
        last applied MLiGDResult, or None when nothing was pending."""
        return self._apply_inflight(fleet, keep=0)

    def engine_slots(self, r_per_slot: float, min_slots: int = 2,
                     max_slots: int = 512) -> np.ndarray:
        """(Z,) int — per-server serving slot counts derived from the
        ledger's admitted r usage (see ``BudgetLedger.slot_counts``).
        The closed-loop data plane sizes its engine pools with this so
        serving capacity tracks what admission actually granted."""
        return self.ledger.slot_counts(r_per_slot, min_slots=min_slots,
                                       max_slots=max_slots)

    def _inflight_rows(self, keep: int) -> int:
        """Rows that ``_apply_inflight(fleet, keep)`` would scatter."""
        n = max(0, len(self._inflight) - max(0, keep))
        return sum(len(p.users) for p in self._inflight[:n])

    def _apply_inflight(self, fleet: FleetState,
                        keep: int = 0) -> Optional[MLiGDResult]:
        """Apply in-flight replans FIFO until at most ``keep`` remain
        (later dispatches win per user, matching the dirty set's
        last-wins contract across steps)."""
        res = None
        while len(self._inflight) > max(0, keep):
            res = self._apply_one(self._inflight.pop(0), fleet)
        return res

    def _apply_one(self, p: _PendingReplan,
                   fleet: FleetState) -> MLiGDResult:
        res, users = p.force(), p.users
        take_back = np.asarray(res.R, bool)
        server = np.where(take_back, p.orig_servers,
                          np.asarray(p.new_server))
        scatter = np.ones(len(users), bool)
        if self.hysteresis > 0.0:
            # switch hysteresis (uncapacitated path): keep the frozen
            # plan row when the re-split doesn't beat the stay/relay
            # continuation by the margin — but never hold a user on a
            # server that has since died
            stay = ~take_back & (np.asarray(res.U_back, np.float64)
                                 <= np.asarray(res.U_recalc, np.float64)
                                 * (1.0 + self.hysteresis))
            if self.topo.faulted:
                stay &= self.topo.server_available()[
                    np.asarray(p.orig_servers, np.int64)]
            p.stayed = int(stay.sum())
            scatter &= ~stay
        if self.topo.faulted:
            live = self.topo.server_available()[server]
            # never scatter onto a dead server: stale rows keep
            # their frozen plan and the next fault preamble evacuates
            # them (on_events routes through _retry_stale_pending
            # first, so this is the drain-without-faults backstop)
            scatter &= live
        if scatter.all():
            fleet.scatter(users, server, res)
            return res
        idx = np.nonzero(scatter)[0]
        if len(idx):
            fleet.scatter(users[idx], server[idx],
                          jax.tree.map(lambda a: a[idx], res))
        return res

    # ------------------------------------------------------------------
    # Fault handling: evacuation replanning (see docs/ARCHITECTURE.md,
    # "Failure handling" + "Event lifecycle", for the dataflow)
    # ------------------------------------------------------------------
    def on_faults(self, batch: FaultBatch, devices: Devices,
                  fleet: FleetState,
                  user_aps: Optional[np.ndarray] = None
                  ) -> EvacuationReport:
        """Failure-aware evacuation replan for one applied FaultBatch —
        a consumer of the :meth:`on_events` pipeline (EVACUATE/DRAIN
        rows, no handoffs).

        Call AFTER ``topo.apply_faults(batch)``.  Every user offloading
        to a down or unreachable server is re-admitted to a surviving
        candidate — the fused dirty-set MLi-GD solve (relay-back priced
        unreachable) plus the water-filling greedy under the budget
        ledger's RESIDUAL headroom — and degraded to device-only
        execution (split = M) when no candidate is reachable or
        admissible.  Device-only users merely *associated* with a dead
        server are re-associated to the nearest up server (no solve:
        they hold no resources).  On capacitated topologies, servers
        whose effective capacity churned below their ledger usage
        additionally DRAIN their overflow users through the same
        pipeline.

        Hysteresis: servers recovered this step are excluded from the
        evacuation target set for ``recovery_hold_steps`` subsequent
        calls (unless they are a user's only survivor), so the fleet
        doesn't flap back the instant a server blips up; static replans
        and natural movement handoffs may still use them.

        Stale async dispatch: an in-flight replan whose decisions would
        land users on a now-dead server is split — still-valid rows are
        applied, stale rows are re-dispatched synchronously against the
        updated topology (``max_replan_retries`` bounds the retries per
        dispatch; exhausted rows fall through to the evacuation).

        ``user_aps``: (X,) current AP per fleet row (``repro.api.
        Session`` passes its mobility state; defaults to the APs of the
        last static plan).  Returns an :class:`EvacuationReport`, also
        kept as ``self.last_evacuation``."""
        events = StepEvents(t=float(getattr(batch, "t", 0.0)),
                            handoffs=HandoffBatch.empty(
                                float(getattr(batch, "t", 0.0))),
                            faults=batch)
        outcome = self.on_events(events, devices, fleet,
                                 user_aps=user_aps, sync=True)
        return outcome.evacuation

    def _nearest_up(self, aps: np.ndarray, up: np.ndarray) -> np.ndarray:
        """Nearest up & reachable server per AP (live hop counts); falls
        back to the lowest-id up server when nothing is reachable from
        an AP (blackout: server 0, deterministically)."""
        h = np.asarray(self.topo.hops[np.asarray(aps)], np.float64).copy()
        h[:, ~up] = np.inf
        best = np.argmin(h, axis=1)
        bad = ~np.isfinite(h[np.arange(len(best)), best])
        if bad.any():
            best[bad] = int(np.argmax(up))
        return best

    def _retry_stale_pending(self, devices: Devices, fleet: FleetState,
                             up: np.ndarray) -> int:
        """Async-dispatch fault safety: split every in-flight replan into
        rows whose decided server survived (applied as usual) and rows
        decided onto a now-dead server (re-dispatched synchronously
        against the updated topology — the retry half of the
        retry-with-backoff wrapper; ``max_replan_retries`` is the
        backoff bound, after which rows fall through to evacuation).
        Returns the number of retried rows."""
        if not self._inflight or up.all():
            return 0
        entries, self._inflight = self._inflight, []
        retried = 0
        for p in entries:
            res_np = p.force()
            final = np.where(np.asarray(res_np.R, bool), p.orig_servers,
                             np.asarray(p.new_server))
            final = np.asarray(final, np.int64)
            stale = ~up[final]
            if not stale.any():
                self._inflight.append(p)  # applies at the next call/drain
                continue
            good = np.nonzero(~stale)[0]
            if len(good):
                fleet.scatter(p.users[good], final[good],
                              jax.tree.map(lambda a: a[good], res_np))
            if p.batch is None or p.attempts >= self.max_replan_retries \
                    or not up.any():
                continue              # out of retries: evacuation owns them
            bad = np.nonzero(stale)[0]
            new_ap = p.batch.new_ap[bad]
            tgt = self._nearest_up(new_ap, up)
            old = np.asarray(fleet.server[p.users[bad]], np.int64)
            retry = HandoffBatch(
                t=p.batch.t, user=p.users[bad],
                old_server=old,
                new_server=np.asarray(tgt, np.int64),
                new_ap=np.asarray(new_ap, np.int64),
                hops_new=clamp_hops(
                    self.topo.hops[new_ap, tgt]).astype(np.int64),
                hops_back=clamp_hops(
                    self.topo.hops[new_ap, old]).astype(np.int64))
            self.replan_retries += len(bad)
            retried += len(bad)
            self.on_handoffs(retry, devices, fleet, sync=True,
                             _attempts=p.attempts + 1)
        return retried

    # ------------------------------------------------------------------
    def run_baseline(self, name: str, devices: Devices,
                     user_aps: np.ndarray):
        user_aps = np.asarray(user_aps)
        servers = self.topo.ap_server[user_aps]
        hops = self.topo.hops[user_aps, servers]
        devs_s = dict(stack_devices(devices))
        devs_s["hops"] = jnp.asarray(hops, jnp.float32)
        return run_baseline_batch(name, self.profile, devs_s,
                                  self._edges_for(servers))
