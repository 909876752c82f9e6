"""Plain reference of the MCSA handoff replan (MLi-GD), in numpy.

Written from the cost model (the paper's Eqs. 1-19 and 41-43 as the
configuration states them) and Algorithm 2: per split point a projected
gradient descent over the normalized (B, r, R, B_back), warm-started from
the previous split, with the paper's stopping rules; the first minimum
over splits; then the two vertices of R.  It imports nothing of the
program and takes no table from it: the layer profile is computed here
from the configuration's layer list, the edge and device parameters come
from the configuration, the rows (who moved where) from the handoff
events the program's mobility layer emitted.

Every function is dtype-generic: float64 is the reference, bfloat16
(``ml_dtypes``) the control.
"""
from __future__ import annotations

import math
from typing import Dict

import numpy as np

LN2 = math.log(2.0)
BITS_PER_ACT = 16
DEVICE_FIELDS = ("c_dev", "xi", "phi", "p_tx", "alpha", "g_fade", "w_T",
                 "w_E", "w_C", "k_rounds")
EDGE_FIELDS = ("c_min", "rho_min", "lam_a", "rho_B", "gamma_B", "B0",
               "B_backhaul", "N0", "B_min", "B_max", "r_min", "r_max")


# ---------------------------------------------------------------------------
# Layer profile from the configuration
# ---------------------------------------------------------------------------
def _chain_cnn(m: dict):
    h = w = m["in_hw"]
    c = m["in_ch"]
    flops, bits = [], []
    for layer in m["layers"]:
        kind, k = layer["kind"], layer.get("kernel", 3)
        if kind == "conv":
            s = layer.get("stride", 1)
            h, w = -(-h // s), -(-w // s)
            f = 2.0 * k * k * c * layer["out_ch"] * h * w
            f += h * w * layer["out_ch"]                     # relu
            c = layer["out_ch"]
        else:
            raise ValueError(f"layer kind {kind!r} is not in this profile")
        flops.append(f)
        bits.append(h * w * c * BITS_PER_ACT)
    return (np.asarray(flops), np.asarray(bits, np.float64),
            float(m["in_hw"] ** 2 * m["in_ch"] * 8), m["num_classes"] * 32.0)


def _transformer_prefill(m: dict, seq: int):
    """One entry per block at ``seq`` prompt tokens: projections, causal
    attention at half the context on average, the MLP; the unembedding
    joins the last block."""
    d, ff, hd = m["d_model"], m["d_ff"], m["head_dim"]
    Hq, Hkv = m["num_heads"], m["num_kv_heads"]
    per = (2.0 * seq * d * (Hq + 2 * Hkv) * hd + 2.0 * seq * Hq * hd * d
           + 4.0 * seq * Hq * hd * seq / 2 + 6.0 * seq * d * ff)
    flops = np.full(m["num_layers"], per)
    flops[-1] += 2.0 * seq * d * m["vocab_size"]
    bits = np.full(m["num_layers"], float(seq * d * BITS_PER_ACT))
    return flops, bits, float(seq * 32), 32.0


def layer_profile(model: dict, planner: dict):
    """(f_l, f_e, w, m_bits): device FLOPs, edge FLOPs and shipped bits at
    each split s = 0..M, and the result size."""
    if model["kind"] == "chain_cnn":
        flops, bits, in_bits, m_bits = _chain_cnn(model)
    elif model["kind"] == "transformer":
        flops, bits, in_bits, m_bits = _transformer_prefill(
            model, planner["model_seq"])
    else:
        raise ValueError(f"unknown model kind {model['kind']!r}")
    cum = np.concatenate([[0.0], np.cumsum(flops)])
    return cum, cum[-1] - cum, np.concatenate([[in_bits], bits]), m_bits


# ---------------------------------------------------------------------------
# Utilities and gradients (normalized coordinates)
# ---------------------------------------------------------------------------
def _u1(p, f_l, f_e, w, xB, xr, dt):
    one = dt(1.0)
    Bs, rs = p["B_max"] - p["B_min"], p["r_max"] - p["r_min"]
    B = p["B_min"] + xB * Bs
    r = p["r_min"] + xr * rs
    offl = dt(1.0) if f_e > 0 else dt(0.0)
    wm = w + p["m"]
    lam = np.power(r, p["lam_a"])
    q = p["p_tx"] * p["alpha"] * p["g_fade"] / p["N0"]
    L = np.log2(one + q / B)
    tau = B * L
    gB = p["rho_B"] * np.power(B / p["B0"], p["gamma_B"])
    T = (f_l / p["c_dev"] + offl * (f_e / (lam * p["c_min"]) + wm / B
                                    + p["hops"] * wm / p["B_backhaul"])
         + p["t_ag"] / p["k_rounds"])
    E = p["xi"] * p["c_dev"] ** 2 * p["phi"] * f_l + \
        offl * p["p_tx"] * wm / tau
    C = offl * (r * p["rho_min"] + gB) / p["k_rounds"]
    U = p["w_T"] * T + p["w_E"] * E + p["w_C"] * C
    dtau = L - q / (dt(LN2) * (B + q))
    dB = offl * (-p["w_T"] * wm / (B * B) - p["w_E"] * p["p_tx"] * wm * dtau
                 / (tau * tau) + p["w_C"] * p["gamma_B"] * gB
                 / (B * p["k_rounds"]))
    dr = offl * (-p["w_T"] * f_e * p["lam_a"] / (lam * r * p["c_min"])
                 + p["w_C"] * p["rho_min"] / p["k_rounds"])
    return U, dB * Bs, dr * rs


def _u2(p, xBb, dt):
    """The relay-back vertex (Eqs. 41-43): the frozen original split and
    rent; the relay crosses the new AP at B_back and ``hops_back`` hops.
    The original split's edge time is priced with the new server's
    ``c_min`` and ``lam_a``, as the configuration's cost model states."""
    one = dt(1.0)
    Bs = p["B_max"] - p["B_min"]
    Bb = p["B_min"] + xBb * Bs
    wm = p["w_o"] + p["m"]
    lam_o = np.power(p["r_o"], p["lam_a"])
    q = p["p_tx"] * p["alpha"] * p["g_fade"] / p["N0"]
    L = np.log2(one + q / Bb)
    tau = Bb * L
    gB = p["rho_B"] * np.power(Bb / p["B0"], p["gamma_B"])
    T = (p["f_l_o"] / p["c_dev"] + p["f_e_o"] / (lam_o * p["c_min"])
         + wm / Bb + p["hops_back"] * wm / p["B_backhaul"])
    E = p["xi"] * p["c_dev"] ** 2 * p["phi"] * p["f_l_o"] + \
        p["p_tx"] * wm / tau
    C = (p["rent_o"] + gB) / p["k_rounds"]
    U = p["w_T"] * T + p["w_E"] * E + p["w_C"] * C
    dtau = L - q / (dt(LN2) * (Bb + q))
    dBb = (-p["w_T"] * wm / (Bb * Bb) - p["w_E"] * p["p_tx"] * wm * dtau
           / (tau * tau) + p["w_C"] * p["gamma_B"] * gB / (Bb * p["k_rounds"]))
    return U, dBb * Bs


def _joint(p, f_l, f_e, w, x, dt):
    xB, xr, R, xBb = x
    U1, g1B, g1r = _u1(p, f_l, f_e, w, xB, xr, dt)
    U2, g2 = _u2(p, xBb, dt)
    one = dt(1.0)
    return ((one - R) * U1 + R * U2,
            ((one - R) * g1B, (one - R) * g1r, U2 - U1, R * g2))


def _gd(p, tab, x, ligd, dt):
    """Projected GD on one split, one lane per row; each lane stops on its
    own rule (gradient norm, utility change or step below eps, or the
    iteration cap)."""
    lr, eps = dt(ligd["lr"]), dt(ligd["eps"])
    zero, one = dt(0.0), dt(1.0)
    u, g = _joint(p, *tab, x, dt)
    n = len(u)
    active = np.ones(n, bool)
    for _ in range(int(ligd["max_iters"])):
        if not active.any():
            break
        x_new = tuple(np.minimum(np.maximum(xi - lr * gi, zero), one)
                      for xi, gi in zip(x, g))
        u_new, g_new = _joint(p, *tab, x_new, dt)
        gnorm = np.sqrt(sum(gi * gi for gi in g))
        dx = np.max(np.stack([np.abs(a - b) for a, b in zip(x_new, x)]), 0)
        stop = (gnorm < eps) | (np.abs(u_new - u) < eps) | (dx < eps)
        x = tuple(np.where(active, a, b) for a, b in zip(x_new, x))
        u = np.where(active, u_new, u)
        g = tuple(np.where(active, a, b) for a, b in zip(g_new, g))
        active &= ~stop
    return x, u


def mligd(p: Dict[str, np.ndarray], prof, ligd: dict, dt=np.float64):
    """The handoff replan of every row: R, split, B, r and U."""
    f_l, f_e, w, _ = prof
    n = len(p["c_dev"])
    x = tuple(np.full(n, v, dt) for v in (*ligd["init"], 0.5, 0.5))
    best_u = np.full(n, np.inf, dt)
    best_s = np.zeros(n, np.int64)
    best_x = x
    for s in range(len(f_l)):
        tab = (dt(f_l[s]), dt(f_e[s]), dt(w[s]))
        x, u = _gd(p, tab, x if ligd["warm_start"] else tuple(
            np.full(n, v, dt) for v in (*ligd["init"], 0.5, 0.5)), ligd, dt)
        better = u < best_u
        best_u = np.where(better, u, best_u)
        best_s = np.where(better, s, best_s)
        best_x = tuple(np.where(better, a, b) for a, b in zip(x, best_x))
    u1 = np.empty(n, dt)
    for s in np.unique(best_s):
        sel = best_s == s
        ps = {k: v[sel] for k, v in p.items()}
        u1[sel] = _u1(ps, dt(f_l[s]), dt(f_e[s]), dt(w[s]),
                      best_x[0][sel], best_x[1][sel], dt)[0]
    u2 = _u2(p, best_x[3], dt)[0]
    back = u2 < u1
    Bs, rs = p["B_max"] - p["B_min"], p["r_max"] - p["r_min"]
    return {"R": back.astype(np.int64),
            "split": np.where(back, p["s_o"], best_s),
            "B": np.where(back, p["B_min"] + best_x[3] * Bs,
                          p["B_min"] + best_x[0] * Bs),
            "r": np.where(back, p["r_o"], p["r_min"] + best_x[1] * rs),
            "U": np.minimum(u1, u2)}


def evaluate(p: Dict[str, np.ndarray], prof, plan: dict):
    """Utility of a stored plan row under the reference model (float64):
    U1 at its split, B and r for a re-split; U2 at its B for a relay."""
    f_l, f_e, w, _ = prof
    dt = np.float64
    split = np.asarray(plan["split"], np.int64)
    B = np.asarray(plan["B"], np.float64)
    r = np.asarray(plan["r"], np.float64)
    Bs, rs = p["B_max"] - p["B_min"], p["r_max"] - p["r_min"]
    out = np.empty(len(split))
    relay = np.asarray(plan["R"]) == 1
    if relay.any():
        ps = {k: v[relay] for k, v in p.items()}
        out[relay] = _u2(ps, (B[relay] - ps["B_min"]) / Bs[relay], dt)[0]
    for s in np.unique(split[~relay]):
        sel = ~relay & (split == s)
        ps = {k: v[sel] for k, v in p.items()}
        out[sel] = _u1(ps, f_l[s], f_e[s], w[s],
                       (B[sel] - ps["B_min"]) / Bs[sel],
                       (r[sel] - ps["r_min"]) / rs[sel], dt)[0]
    return out


def rows(cfg: dict, prof, c_dev, hops_new, hops_back, new_server,
         orig_server, orig_split, orig_r, orig_B, t_ag: float, dt=np.float64):
    """Per-row parameter arrays of one step's handoffs."""
    f_l, f_e, w, m_bits = prof
    servers = cfg["topology"]["servers"]
    dev = cfg["devices"]
    n = len(c_dev)

    def col(v):
        return np.broadcast_to(np.asarray(v, np.float64), (n,)).astype(dt)

    def edge(field, idx):
        return col(np.array([s[field] for s in servers])[idx])

    p = {k: col(dev[k]) for k in DEVICE_FIELDS if k != "c_dev"}
    p["c_dev"] = col(c_dev)
    p["t_ag"] = col(t_ag)
    p["hops"] = col(hops_new)
    p["hops_back"] = col(hops_back)
    p["m"] = col(m_bits)
    for k in EDGE_FIELDS:
        p[k] = edge(k, new_server)
    s_o = np.asarray(orig_split, np.int64)
    r_true = np.asarray(orig_r, np.float64)
    B_o = np.asarray(orig_B, np.float64)
    rho_min_o = np.array([s["rho_min"] for s in servers])[orig_server]
    rho_B_o = np.array([s["rho_B"] for s in servers])[orig_server]
    B0_o = np.array([s["B0"] for s in servers])[orig_server]
    gam_o = np.array([s["gamma_B"] for s in servers])[orig_server]
    p["rent_o"] = col(r_true * rho_min_o + rho_B_o * (B_o / B0_o) ** gam_o)
    p["r_o"] = col(np.where(r_true > 0, r_true, 1.0))
    p["f_l_o"] = col(f_l[s_o])
    p["f_e_o"] = col(f_e[s_o])
    p["w_o"] = col(w[s_o])
    p["s_o"] = s_o
    return p
