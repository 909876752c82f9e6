"""MCSA cost models — faithful implementations of the paper's Eqs. (1)–(17).

Everything is differentiable jnp over the continuous variables (B, r) so the
Li-GD / MLi-GD solvers can take exact gradients; the discrete split ``s``
enters only through precomputed per-layer prefix profiles (the paper's
``f_l^i``, ``f_e^i``, ``w_{s_i}`` — "calculated by mobile users in advance
and stored ... with the inference model").

Units: FLOPs for compute, bits for data, Hz for bandwidth, Watts for power,
seconds / Joules / $ for the three objectives.

Paper-faithfulness notes
------------------------
* Delay (Eq. 5): device→AP hop uses the *allocated* bandwidth ``B_i``
  directly and the AP→server relay uses the backhaul ``B`` per hop, exactly
  as Eq. (5).
* Energy (Eq. 12): transmit energy uses the Shannon rate τ(B) (Eq. 11) with
  the (w_s + m) payload of Eq. (10)/(12).  (Eq. 18 drops ``m`` from the
  energy term; we keep Eq. 12's form and note the discrepancy.)
* Edge execution (Eq. 3): non-linear multicore speedup λ(r) = r^a (a < 1,
  monotone, concave — the paper only assumes "increases with r, but not
  linear", citing [15]'s ≤44 % error for the linear model).
* Renting (Eq. 13–16): C = r·ρ_min + g(B) with convex g(B) = ρ_B·(B/B0)^γ,
  amortized per round: CBR_C = C/k (Eq. 16).
* Strategy-calculation delay enters as CBR = T_ag/k (Eq. 7), a constant
  w.r.t. (B, r) — it shifts utilities but not gradients, exactly as in
  Eq. (18)'s T_ag^i/k_i term.
"""
from __future__ import annotations

import dataclasses
import hashlib
from typing import Dict, Optional, Sequence, Tuple, Union

import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class DeviceParams:
    """Per-mobile-user parameters (paper's user i)."""
    c_dev: float = 25e9          # device FLOP/s (c_i)
    xi: float = 3e-31            # effective switched capacitance (ξ_i);
                                 # ξ·c²·φ ≈ 2e-10 J/FLOP ≈ 5 GFLOPS/W
    phi: float = 1.0             # cycles per FLOP (φ_i folded to FLOP basis)
    p_tx: float = 0.5            # transmit power, W (p_i)
    alpha: float = 1e-10         # large-scale fading power gain (α_i^κ)
    g_fade: float = 1.0          # small-scale fading (g_i^κ)
    w_T: float = 1 / 3           # ω_T
    w_E: float = 1 / 3           # ω_E
    w_C: float = 1 / 3           # ω_C
    k_rounds: float = 50.0       # k_i — task rounds at this server
    t_ag: float = 0.0            # T_Ag — strategy calculation time (s)
    hops: int = 1                # H_i — AP hops to the edge server

    def as_array(self) -> np.ndarray:
        return np.array([self.c_dev, self.xi, self.phi, self.p_tx,
                         self.alpha, self.g_fade, self.w_T, self.w_E,
                         self.w_C, self.k_rounds, self.t_ag,
                         float(self.hops)], np.float64)


DEV_FIELDS = ("c_dev", "xi", "phi", "p_tx", "alpha", "g_fade",
              "w_T", "w_E", "w_C", "k_rounds", "t_ag", "hops")


@dataclasses.dataclass(frozen=True)
class EdgeParams:
    """Per-edge-server parameters (paper's server j)."""
    c_min: float = 50e9          # FLOP/s of one minimum compute unit
    rho_min: float = 2e-4        # $/s per rented unit (ρ_min^j)
    lam_a: float = 0.85          # λ(r) = r^lam_a  (multicore sub-linearity)
    rho_B: float = 1e-4          # bandwidth price scale
    gamma_B: float = 1.2         # bandwidth price convexity (g convex)
    B0: float = 1e6              # bandwidth price normalizer (Hz)
    B_backhaul: float = 1e9      # inter-AP backhaul bandwidth B (bit/s)
    N0: float = 4e-21            # noise PSD (W/Hz)
    B_min: float = 1e6
    B_max: float = 2e7
    r_min: float = 1.0
    r_max: float = 32.0

    def as_array(self) -> np.ndarray:
        return np.array([self.c_min, self.rho_min, self.lam_a, self.rho_B,
                         self.gamma_B, self.B0, self.B_backhaul, self.N0,
                         self.B_min, self.B_max, self.r_min, self.r_max],
                        np.float64)


EDGE_FIELDS = ("c_min", "rho_min", "lam_a", "rho_B", "gamma_B", "B0",
               "B_backhaul", "N0", "B_min", "B_max", "r_min", "r_max")


@dataclasses.dataclass(frozen=True)
class LayerProfile:
    """Per-layer workload profile of one model (paper's f / w tables).

    flops[j]    — FLOPs of layer j (j = 0..M-1)
    out_bits[j] — intermediate-activation size emitted by layer j (w_{s}) —
                  the data shipped if we split AFTER layer j+1 ... i.e.
                  split s means layers [0, s) on device; the tensor shipped
                  is the output of layer s-1, ``out_bits[s-1]``; s=0 ships
                  the raw input ``in_bits``.
    in_bits     — raw input size (shipped for Edge-Only / s=0)
    result_bits — final inference result size (m_i)
    """
    name: str
    flops: np.ndarray
    out_bits: np.ndarray
    in_bits: float
    result_bits: float

    @property
    def num_layers(self) -> int:
        return len(self.flops)

    def prefix_tables(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(f_l[s], f_e[s], w[s]) for s = 0..M: device FLOPs, edge FLOPs,
        shipped bits at each split point."""
        M = self.num_layers
        cum = np.concatenate([[0.0], np.cumsum(self.flops)])
        f_l = cum                              # s = 0..M
        f_e = cum[-1] - cum
        w = np.concatenate([[self.in_bits], self.out_bits])
        return f_l, f_e, w

    @property
    def fingerprint(self) -> str:
        """Content hash — the sound cache key for jitted solvers.  Keying
        by ``id(profile)`` is unsound: ids are reused after gc, so a dead
        profile's compiled solve (closing over ITS tables) could serve a
        fresh profile with different workloads."""
        fp = self.__dict__.get("_fingerprint")
        if fp is None:
            h = hashlib.sha1()
            h.update(self.name.encode())
            for arr in (self.flops, self.out_bits,
                        (self.in_bits, self.result_bits)):
                a = np.ascontiguousarray(np.asarray(arr, np.float64))
                # length-prefix each field: without it, bytes sliding from
                # flops into out_bits would collide
                h.update(np.int64(a.size).tobytes())
                h.update(a.tobytes())
            fp = h.hexdigest()
            object.__setattr__(self, "_fingerprint", fp)
        return fp


# ---------------------------------------------------------------------------
# Differentiable cost terms.  dev/edge are dicts of scalars (or batched
# arrays under vmap) keyed as DEV_FIELDS / EDGE_FIELDS.
# ---------------------------------------------------------------------------
def lam(edge, r):
    """λ(r): sub-linear multicore speedup (Eq. 3 compensation function)."""
    return jnp.power(r, edge["lam_a"])


def shannon_rate(dev, edge, B):
    """τ_i = B log2(1 + p α g / (B N0))  (Eq. 11), bits/s."""
    snr = dev["p_tx"] * dev["alpha"] * dev["g_fade"] / (B * edge["N0"])
    return B * jnp.log2(1.0 + snr)


def t_device(dev, f_l):
    """Eq. (1): on-device inference delay."""
    return f_l / dev["c_dev"]


def t_server(dev, edge, f_e, r):
    """Eq. (3): edge inference delay with λ(r) compensation."""
    return f_e / (lam(edge, r) * edge["c_min"])


def t_transmit(dev, edge, w_bits, m_bits, B, hops=None):
    """Eq. (5): device→AP (allocated B) + per-hop AP relay (backhaul)."""
    h = dev["hops"] if hops is None else hops
    t_up = (w_bits + m_bits) / B
    t_relay = h * (w_bits + m_bits) / edge["B_backhaul"]
    return t_up + t_relay


def relay_seconds(bits, hops, B_backhaul):
    """The backhaul relay term of Eq. (5) / Eq. (41)'s H₂ path on an
    arbitrary payload: ship ``bits`` over ``hops`` AP→server hops at
    ``B_backhaul`` bit/s each.  The serving layer prices BOTH mid-stream
    failover mechanisms with this one formula — token activations for a
    re-prefill, the actual KV-cache leaves for a migration — so the
    data plane's bytes-vs-recompute decision uses the planner's own
    cost model (see :mod:`repro.serving.failover`)."""
    return float(bits) * float(hops) / float(B_backhaul)


def cbr_calc(dev):
    """Eq. (7): strategy-calculation cost-benefit ratio T_Ag / k."""
    return dev["t_ag"] / dev["k_rounds"]


def energy_compute(dev, f_l):
    """Eq. (9): E^l = ξ c² φ f  (paper-literal; φ in cycles/FLOP)."""
    return dev["xi"] * dev["c_dev"] ** 2 * dev["phi"] * f_l


def energy_transmit(dev, edge, w_bits, m_bits, B):
    """Eq. (10): E^t = p · (w_s + m) / τ(B)."""
    return dev["p_tx"] * (w_bits + m_bits) / shannon_rate(dev, edge, B)


def energy(dev, edge, f_l, w_bits, m_bits, B):
    """Eq. (12): total device energy."""
    return (energy_compute(dev, f_l)
            + energy_transmit(dev, edge, w_bits, m_bits, B))


def rent_cost(edge, r, B):
    """Eq. (15): C = r ρ_min + g(B), convex increasing g."""
    g_B = edge["rho_B"] * jnp.power(B / edge["B0"], edge["gamma_B"])
    return r * edge["rho_min"] + g_B


def utility(dev, edge, f_l, f_e, w_bits, m_bits, B, r, *, offloaded=None):
    """Eq. (17)/(19): U = ω_T·T + ω_E·E + ω_C·CBR_C for one split point.

    ``offloaded``: 0/1 (or soft) indicator that any work is offloaded —
    when s = M (device-only) there is no transmission, no renting, no edge
    compute.  Passing ``offloaded=None`` derives it from f_e > 0.
    """
    if offloaded is None:
        offloaded = jnp.where(f_e > 0, 1.0, 0.0)
    T = (t_device(dev, f_l)
         + offloaded * (t_server(dev, edge, f_e, r)
                        + t_transmit(dev, edge, w_bits, m_bits, B))
         + cbr_calc(dev))
    E = (energy_compute(dev, f_l)
         + offloaded * energy_transmit(dev, edge, w_bits, m_bits, B))
    C = offloaded * rent_cost(edge, r, B) / dev["k_rounds"]
    U = dev["w_T"] * T + dev["w_E"] * E + dev["w_C"] * C
    return U, (T, E, C)


class DeviceFleet:
    """Struct-of-arrays :class:`DeviceParams` for a fleet of X users.

    The array-resident input the vectorized planner consumes: every field
    of DEV_FIELDS is a (X,) float64 numpy array, so 100k+ users never
    materialize 100k Python dataclasses.  Missing fields broadcast from the
    ``DeviceParams`` defaults."""

    __slots__ = ("arrays",)

    def __init__(self, num_users: Optional[int] = None, **fields):
        unknown = set(fields) - set(DEV_FIELDS)
        if unknown:
            raise TypeError(f"unknown device fields: {sorted(unknown)}")
        if num_users is None:
            sizes = [np.ndim(v) and len(np.asarray(v)) for v in
                     fields.values()]
            sizes = [s for s in sizes if s]
            if not sizes:
                raise TypeError("DeviceFleet needs num_users or at least "
                                "one array-valued field")
            num_users = sizes[0]
        defaults = DeviceParams()
        self.arrays: Dict[str, np.ndarray] = {}
        for k in DEV_FIELDS:
            v = np.asarray(fields.get(k, getattr(defaults, k)), np.float64)
            self.arrays[k] = np.ascontiguousarray(
                np.broadcast_to(v, (num_users,)))

    @classmethod
    def from_params(cls, devs: Sequence[DeviceParams]) -> "DeviceFleet":
        return cls(num_users=len(devs),
                   **{k: np.asarray([getattr(d, k) for d in devs],
                                    np.float64) for k in DEV_FIELDS})

    def __len__(self) -> int:
        return len(self.arrays["c_dev"])

    def __getitem__(self, i: int) -> DeviceParams:
        kw = {k: float(v[i]) for k, v in self.arrays.items()}
        kw["hops"] = int(kw["hops"])
        return DeviceParams(**kw)

    def replace(self, **fields) -> "DeviceFleet":
        arrays = dict(self.arrays)
        for k, v in fields.items():
            if k not in DEV_FIELDS:
                raise TypeError(f"unknown device field: {k}")
            arrays[k] = np.ascontiguousarray(np.broadcast_to(
                np.asarray(v, np.float64), (len(self),)))
        out = DeviceFleet.__new__(DeviceFleet)
        out.arrays = arrays
        return out


Devices = Union[DeviceFleet, Sequence[DeviceParams]]


def dev_dict(d: DeviceParams) -> dict:
    return {k: jnp.asarray(getattr(d, k), jnp.float32) for k in DEV_FIELDS}


def edge_dict(e: EdgeParams) -> dict:
    return {k: jnp.asarray(getattr(e, k), jnp.float32) for k in EDGE_FIELDS}


def stack_devices(devs: Devices) -> dict:
    """(X,)-leading-axis device dict from a DeviceFleet (O(fields), no
    per-user work) or a sequence of DeviceParams (legacy path)."""
    if isinstance(devs, DeviceFleet):
        return {k: jnp.asarray(v, jnp.float32)
                for k, v in devs.arrays.items()}
    return {k: jnp.asarray([getattr(d, k) for d in devs], jnp.float32)
            for k in DEV_FIELDS}


def gather_devices(devs: Devices, idx: np.ndarray) -> dict:
    """Stacked device dict for the ``idx`` rows only — O(len(idx)), never
    O(fleet): handoff steps must not pay for users who didn't move."""
    if isinstance(devs, DeviceFleet):
        return {k: jnp.asarray(v[idx], jnp.float32)
                for k, v in devs.arrays.items()}
    return stack_devices([devs[int(i)] for i in idx])


def gather_devices_np(devs: Devices, idx: np.ndarray
                      ) -> Dict[str, np.ndarray]:
    """:func:`gather_devices` on the host: the same float32 rows as numpy
    arrays, for a caller that pads and transfers the batch itself."""
    if isinstance(devs, DeviceFleet):
        return {k: v[idx].astype(np.float32)
                for k, v in devs.arrays.items()}
    return {k: np.asarray([getattr(devs[int(i)], k) for i in idx],
                          np.float32) for k in DEV_FIELDS}


def stack_edges(edges) -> dict:
    return {k: jnp.asarray([getattr(e, k) for e in edges], jnp.float32)
            for k in EDGE_FIELDS}


def stack_edges_np(edges) -> Dict[str, np.ndarray]:
    """Host-resident (Z,) edge-parameter table — built once per topology,
    gathered per user with fancy indexing (no per-user Python)."""
    return {k: np.asarray([getattr(e, k) for e in edges], np.float64)
            for k in EDGE_FIELDS}


def apply_congestion(edge_table: Dict[str, np.ndarray],
                     compute_mult=None,
                     backhaul_mult=None) -> Dict[str, np.ndarray]:
    """Congestion-adjusted copy of a :func:`stack_edges_np` table.

    The telemetry loop's belief about realized load enters the cost
    model here and only here: ``c_min`` (the per-unit compute rate of
    Eq. 3) is divided by ``compute_mult`` and ``B_backhaul`` (the relay
    bandwidth of Eq. 5 / Eq. 41) by ``backhaul_mult``, so a congested
    server *looks slower and farther away* to every downstream cost —
    t_server, t_transmit, relay_seconds — without touching the formulas
    themselves.  Multipliers are (Z,) vectors in ``[1, max_mult]``
    (see :class:`repro.telemetry.LoadSnapshot`); values below 1 are
    clipped up — observed congestion can only *shrink* believed
    capacity, never inflate it past the static rating.

    Identity multipliers (or None) return ``edge_table`` itself, same
    object — the ``feedback=off`` path stays pointer-equal to the
    static table, which is what pins those trajectories bit-for-bit.
    """
    cm = None if compute_mult is None else np.maximum(
        np.asarray(compute_mult, np.float64), 1.0)
    bm = None if backhaul_mult is None else np.maximum(
        np.asarray(backhaul_mult, np.float64), 1.0)
    if ((cm is None or np.all(cm == 1.0))
            and (bm is None or np.all(bm == 1.0))):
        return edge_table
    out = dict(edge_table)
    if cm is not None:
        out["c_min"] = np.asarray(out["c_min"], np.float64) / cm
    if bm is not None:
        out["B_backhaul"] = (np.asarray(out["B_backhaul"], np.float64)
                             / bm)
    return out
