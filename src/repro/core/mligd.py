"""MLi-GD: Mobility-aware Li-GD (paper Algorithm 2, §5).

When a user moves into a new edge server's coverage it chooses between:
  R=0  re-solve (s, B, r) against the NEW server (Li-GD, Eq. 18), or
  R=1  keep the original split/server and relay the intermediate data back
       over the new AP's allocated bandwidth B_back and H₂ backhaul hops
       (Eq. 41–43).

R ∈ {0,1} is relaxed to [0,1]; U = (1-R)·U₁ + R·U₂ is affine in R so the
optimum sits at a vertex and the relaxation is exact (Corollary 7) — after
the joint GD we evaluate both vertices and pick the min, which is also how
the ε-approximation claim is realized.

Variables: x = (B_norm, r_norm, R, B_back_norm) ∈ [0,1]⁴, optimized jointly
with the same warm-started layer loop as Li-GD (only U₁ depends on s; U₂'s
split is frozen at the original strategy, paper §5: "the model segmentation
strategy in the second term does not change").

Like Li-GD, the batched solve dispatches on ``LiGDConfig.solver``: the
default ``"fused"`` path runs the whole-sweep joint kernel from
``repro.kernels.ligd_step`` (4-variable variant, closed-form gradients,
per-lane convergence masking) and evaluates the two R vertices outside the
kernel; ``"autodiff"`` keeps the vmapped scan+while oracle below.

Batch rows are (device, new-edge, frozen-orig) triples with no identity
of their own, so the planner's candidate-aware replanning tiles one
handoff event into K rows — one per candidate server of the new AP, edge
and hop leaves gathered per row — and reduces with an argmin over U
afterwards; see MCSAPlanner.on_handoffs and docs/ARCHITECTURE.md.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from .costs import LayerProfile, energy_compute, energy_transmit, rent_cost, \
    t_device, t_server
from .ligd import LiGDConfig, LiGDResult, _denorm, _gd_solve, \
    make_split_utility


class MLiGDResult(NamedTuple):
    R: jnp.ndarray               # 0 = re-solve at new server, 1 = relay back
    split: jnp.ndarray           # s* (new split if R=0, original if R=1)
    B: jnp.ndarray               # bandwidth at the serving AP (Hz)
    r: jnp.ndarray               # compute units at the serving server
    U: jnp.ndarray
    T: jnp.ndarray
    E: jnp.ndarray
    C: jnp.ndarray
    U_recalc: jnp.ndarray        # vertex utilities (diagnostics)
    U_back: jnp.ndarray
    iters_per_layer: jnp.ndarray


def u_transmit_back(dev, edge_new, orig, m_bits, B_back, hops_back):
    """U₂ (Eq. 41–43): original device+edge terms are constant; only the
    relay transmission through the new AP varies.

    orig: dict with the frozen original strategy
      {f_l, f_e, w (bits at original split), r (units), B (orig bandwidth),
       rent (orig per-round rent $)}.
    """
    w = orig["w"]
    T = (t_device(dev, orig["f_l"])
         + t_server(dev, edge_new, orig["f_e"], orig["r"])
         + (w + m_bits) / B_back
         + hops_back * (w + m_bits) / edge_new["B_backhaul"])
    E = (energy_compute(dev, orig["f_l"])
         + energy_transmit(dev, edge_new, w, m_bits, B_back))
    # original server rent is unchanged; the new AP's bandwidth is rented.
    gB = edge_new["rho_B"] * jnp.power(
        B_back / edge_new["B0"], edge_new["gamma_B"])
    C = (orig["rent"] + gB) / dev["k_rounds"]
    U = dev["w_T"] * T + dev["w_E"] * E + dev["w_C"] * C
    return U, (T, E, C)


def solve_mligd(profile: LayerProfile, dev, edge_new, orig, hops_back,
                cfg: LiGDConfig = LiGDConfig()) -> MLiGDResult:
    """Joint (s, B, r, R, B_back) solve for one user after a handoff
    (autodiff oracle).

    edge_new: the NEW server's parameters (dev['hops'] must already be the
    hop count to the new server).  hops_back: H₂ hops from the new AP back
    to the ORIGINAL server.  orig: frozen original strategy (see
    u_transmit_back).
    """
    f_l_np, f_e_np, w_np = profile.prefix_tables()
    f_l = jnp.asarray(f_l_np, jnp.float32)
    f_e = jnp.asarray(f_e_np, jnp.float32)
    w = jnp.asarray(w_np, jnp.float32)
    m_bits = jnp.asarray(profile.result_bits, jnp.float32)
    M1 = len(f_l_np)
    u1_fn = make_split_utility(dev, edge_new, f_l, f_e, w, m_bits)

    def joint_u(s, x4):
        u1, _ = u1_fn(s, x4[:2])
        B_back = edge_new["B_min"] + x4[3] * (edge_new["B_max"]
                                              - edge_new["B_min"])
        u2, _ = u_transmit_back(dev, edge_new, orig, m_bits, B_back,
                                hops_back)
        R = x4[2]
        return (1.0 - R) * u1 + R * u2

    def layer_step(carry_x, s):
        x0 = carry_x if cfg.warm_start else jnp.asarray(
            (*cfg.init, 0.5, 0.5), jnp.float32)
        x, u, it = _gd_solve(lambda x: joint_u(s, x), x0, cfg)
        return x, (u, x, it)

    x_init = jnp.asarray((*cfg.init, 0.5, 0.5), jnp.float32)
    _, (U_all, X_all, iters) = jax.lax.scan(layer_step, x_init,
                                            jnp.arange(M1))

    # Corollary 7: evaluate both vertices of R with the solved continuous
    # variables; the relaxation optimum is at one of them.
    best_s = jnp.argmin(U_all)
    x_best = X_all[best_s]
    u1_star, (T1, E1, C1) = u1_fn(best_s, x_best[:2])
    B_back = edge_new["B_min"] + x_best[3] * (edge_new["B_max"]
                                              - edge_new["B_min"])
    u2_star, (T2, E2, C2) = u_transmit_back(dev, edge_new, orig, m_bits,
                                            B_back, hops_back)
    take_back = u2_star < u1_star
    B1, r1 = _denorm(edge_new, x_best[:2])
    return MLiGDResult(
        R=take_back.astype(jnp.int32),
        split=jnp.where(take_back, orig["split"], best_s),
        B=jnp.where(take_back, B_back, B1),
        r=jnp.where(take_back, orig["r"], r1),
        U=jnp.minimum(u1_star, u2_star),
        T=jnp.where(take_back, T2, T1),
        E=jnp.where(take_back, E2, E1),
        C=jnp.where(take_back, C2, C1),
        U_recalc=u1_star, U_back=u2_star,
        iters_per_layer=iters)


def _solve_mligd_fused(profile: LayerProfile, devs, edge_new, origs,
                       hops_back, cfg: LiGDConfig) -> MLiGDResult:
    """Batched fused joint sweep + the Corollary-7 vertex pick.

    devs/origs leaves are (X,); edge_new leaves are (X,) or shared."""
    # Lazy import: repro.kernels imports repro.core.costs at module load.
    from repro.kernels.ligd_step import (mligd_sweep, pack_sweep_features,
                                         sweep_tables)
    f_l_np, f_e_np, w_np = profile.prefix_tables()
    f_l = jnp.asarray(f_l_np, jnp.float32)
    f_e = jnp.asarray(f_e_np, jnp.float32)
    w = jnp.asarray(w_np, jnp.float32)
    m_bits = jnp.asarray(profile.result_bits, jnp.float32)

    X = devs["c_dev"].shape[0]
    hops_back = jnp.asarray(hops_back, jnp.float32)
    feat = pack_sweep_features(devs, edge_new, m_bits, X, orig=origs,
                               hops_back=hops_back)
    init4 = (*cfg.init, 0.5, 0.5)
    x0 = jnp.broadcast_to(
        jnp.asarray(init4, jnp.float32)[:, None], (4, X))
    res = mligd_sweep(feat, x0, sweep_tables(profile), lr=cfg.lr,
                      eps=cfg.eps, max_iters=cfg.max_iters, chunk=cfg.chunk,
                      warm_start=cfg.warm_start, init=init4)

    xB, xr, xR, xBb = res.best_x
    u1_fn = make_split_utility(devs, edge_new, f_l, f_e, w, m_bits)
    u1_star, (T1, E1, C1) = u1_fn(res.best_s, (xB, xr))
    B_back = edge_new["B_min"] + xBb * (edge_new["B_max"]
                                        - edge_new["B_min"])
    u2_star, (T2, E2, C2) = u_transmit_back(devs, edge_new, origs, m_bits,
                                            B_back, hops_back)
    take_back = u2_star < u1_star
    B1, r1 = _denorm(edge_new, (xB, xr))
    return MLiGDResult(
        R=take_back.astype(jnp.int32),
        split=jnp.where(take_back, origs["split"], res.best_s),
        B=jnp.where(take_back, B_back, B1),
        r=jnp.where(take_back, origs["r"], r1),
        U=jnp.minimum(u1_star, u2_star),
        T=jnp.where(take_back, T2, T1),
        E=jnp.where(take_back, E2, E1),
        C=jnp.where(take_back, C2, C1),
        U_recalc=u1_star, U_back=u2_star,
        iters_per_layer=res.iters_layers.T.astype(jnp.int32))


def orig_strategy_dict(profile: LayerProfile, edge_orig, res: LiGDResult):
    """Freeze a Li-GD solution into the ``orig`` dict MLi-GD consumes."""
    f_l_np, f_e_np, w_np = profile.prefix_tables()
    f_l = jnp.asarray(f_l_np, jnp.float32)
    f_e = jnp.asarray(f_e_np, jnp.float32)
    w = jnp.asarray(w_np, jnp.float32)
    s = res.split
    return {
        "split": s,
        "f_l": f_l[s],
        "f_e": f_e[s],
        "w": w[s],
        "r": res.r,
        "B": res.B,
        "rent": rent_cost(edge_orig, res.r, res.B),
    }


def solve_mligd_batch(profile: LayerProfile, devs, edge_new, origs,
                      hops_back, cfg: LiGDConfig = LiGDConfig()
                      ) -> MLiGDResult:
    """Batched handoff solve; dispatches on ``cfg.solver``."""
    if cfg.solver == "fused":
        return _solve_mligd_fused(profile, devs, edge_new, origs,
                                  hops_back, cfg)
    if cfg.solver != "autodiff":
        raise ValueError(f"unknown LiGDConfig.solver: {cfg.solver!r}")
    edge_batched = jnp.ndim(next(iter(edge_new.values()))) > 0
    in_axes = (0, 0 if edge_batched else None, 0, 0)
    fn = jax.vmap(
        lambda d, e, o, h: solve_mligd(profile, d, e, o, h, cfg),
        in_axes=in_axes)
    return fn(devs, edge_new, origs, hops_back)


_CACHE: dict = {}


def with_rent(origs, edge_orig):
    """``origs`` with its ``rent`` leaf (Eq. 15) priced at the original
    server's rows ``edge_orig`` (``rho_min``, ``rho_B``, ``B0``,
    ``gamma_B``) from the truly rented units ``origs["r_true"]`` — the
    ``r`` leaf may hold a stand-in (see MCSAPlanner._solve_dirty)."""
    out = {k: v for k, v in origs.items() if k != "r_true"}
    out["rent"] = rent_cost(edge_orig, origs["r_true"], origs["B"])
    return out


def solve_mligd_batch_jit(profile: LayerProfile, devs, edge_new, origs,
                          hops_back, cfg: LiGDConfig = LiGDConfig(),
                          edge_orig=None) -> MLiGDResult:
    """jit-cached batched solve; edge_new may be shared or per-user.
    Cache keyed by profile content, not id() (see LayerProfile.fingerprint).

    With ``edge_orig`` given, ``origs`` carries ``r_true`` in place of
    ``rent`` and the rent is computed inside the jitted solve
    (:func:`with_rent`), so a caller that hands over host arrays runs no
    eager device operation before the dispatch."""
    edge_batched = jnp.ndim(next(iter(edge_new.values()))) > 0
    key = (profile.fingerprint, cfg, edge_batched)
    fn = _CACHE.get(key)
    if fn is None:
        fn = jax.jit(lambda d, e, o, h, eo: solve_mligd_batch(
            profile, d, e, o if eo is None else with_rent(o, eo), h, cfg))
        _CACHE[key] = fn
    return fn(devs, edge_new, origs, hops_back, edge_orig)
