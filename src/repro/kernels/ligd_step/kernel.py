"""Pallas-TPU batched Li-GD kernels — the paper's compute hot-spot.

The MCSA planner at an edge server solves (B, r) for EVERY attached user ×
EVERY candidate split layer (X·M GD solves, Corollary 3's X·K̄·M cost).
Each solve is a tiny independent optimization — an embarrassingly-parallel
VPU workload, not an MXU one.

Two generations of kernel live here:

* ``ligd_steps_tpu`` — the original SINGLE-STEP-LOOP kernel: K fixed
  projected-GD steps for one split point per launch, per-batch-constant
  edge params.  Kept as the minimal exemplar and for its tests.

* ``ligd_sweep_tpu`` / ``mligd_sweep_tpu`` — the FUSED WHOLE-SWEEP
  kernels (the planner's hot path): one launch carries the entire M+1
  split sweep per user in kernel — warm-starting split s+1 from split s's
  optimum (the Li-GD trick), closed-form gradients, per-lane convergence
  masking (chunked fixed-iteration steps + early-exit counters instead of
  a lockstep while_loop), and a running in-kernel argmin over splits.
  The MLi-GD variant optimizes the joint (B, r, R, B_back) objective of
  Eq. 41–43.  Features are laid out (NF_SWEEP, X) — users on lanes —
  and each block folds its xb users onto full (8, xb/8) VPU tiles, so
  every per-user quantity fills whole vregs; the per-split
  prefix tables are compile-time constants (the split loop is unrolled),
  and edge parameters are PER-USER feature rows, so one launch serves a
  fleet attached to heterogeneous servers.  The per-row edge layout is
  also what makes the planner's (user, candidate) admission batching a
  pure gather: X·K rows with candidate-gathered edge columns go through
  the SAME kernel unchanged (docs/ARCHITECTURE.md, "Admission control").
  The step arithmetic is imported from ``ref.py`` — the dense reference
  and the kernel run the same ops, so parity is arithmetic identity.

Single-step feature layout per user (NF = 16):
  0:f_l  1:f_e  2:w_bits  3:m_bits  4:offloaded  5:c_dev  6:xi·c²·φ
  7:p_tx  8:c1(=pαg/N0)  9:hops  10:k_rounds  11:t_ag  12:w_T  13:w_E
  14:w_C  15:x0_B (warm start)   [16:x0_r packed in a second array]

Edge scalars are compile-time-constant across a server's user batch and
enter as kernel params (c_min, ρ, a, ρ_B, γ, B0, B_backhaul, bounds).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import NF_SWEEP, SWEEP_FIELDS, _init_x, _layer_solve

NF = 16
LN2 = math.log(2.0)


def _utility_terms(feat, xB, xr, ep):
    """U and dU/d(xB, xr) in normalized coordinates — closed form."""
    f_l, f_e, w, m, offl = (feat[..., i] for i in range(5))
    c_dev, e_per_flop, p_tx, c1, hops, k_rounds, t_ag = (
        feat[..., i] for i in range(5, 12))
    wT, wE, wC = (feat[..., i] for i in range(12, 15))

    B_span = ep["B_max"] - ep["B_min"]
    r_span = ep["r_max"] - ep["r_min"]
    B = ep["B_min"] + xB * B_span
    r = ep["r_min"] + xr * r_span

    wm = w + m
    lam = jnp.power(r, ep["lam_a"])
    q = c1 / ep["N0"]                              # pαg/N0
    L = jnp.log1p(q / B) / LN2                     # log2(1 + pαg/(B·N0))
    tau = B * L
    gB = ep["rho_B"] * jnp.power(B / ep["B0"], ep["gamma_B"])

    T = (f_l / c_dev
         + offl * (f_e / (lam * ep["c_min"])
                   + wm / B + hops * wm / ep["B_backhaul"])
         + t_ag / k_rounds)
    E = e_per_flop * f_l + offl * p_tx * wm / tau
    C = offl * (r * ep["rho_min"] + gB) / k_rounds
    U = wT * T + wE * E + wC * C

    # dτ/dB = L - q / (ln2 · (B + q))
    dtau = L - q / (LN2 * (B + q))
    dU_dB = (wT * offl * (-wm / (B * B))
             + wE * offl * p_tx * wm * (-dtau / (tau * tau))
             + wC * offl * ep["rho_B"] * ep["gamma_B"]
             * jnp.power(B / ep["B0"], ep["gamma_B"]) / (B * k_rounds))
    dU_dr = (wT * offl * f_e / ep["c_min"]
             * (-ep["lam_a"]) * jnp.power(r, -ep["lam_a"] - 1.0)
             + wC * offl * ep["rho_min"] / k_rounds)
    return U, dU_dB * B_span, dU_dr * r_span


def _ligd_kernel(feat_ref, x0_ref, x_ref, u_ref, *, iters: int, lr: float,
                 ep: dict):
    feat = feat_ref[...].astype(jnp.float32)       # (xb, NF)
    x = x0_ref[...].astype(jnp.float32)            # (xb, 2)

    def step(_, x):
        _, gB, gr = _utility_terms(feat, x[:, 0], x[:, 1], ep)
        g = jnp.stack([gB, gr], axis=-1)
        return jnp.clip(x - lr * g, 0.0, 1.0)

    x = jax.lax.fori_loop(0, iters, step, x)
    u, _, _ = _utility_terms(feat, x[:, 0], x[:, 1], ep)
    x_ref[...] = x
    u_ref[...] = u[:, None]


@functools.partial(jax.jit, static_argnames=(
    "iters", "lr", "user_block", "interpret", "edge_tuple"))
def ligd_steps_tpu(feat, x0, *, edge_tuple, iters: int = 64,
                   lr: float = 0.15, user_block: int = 1024,
                   interpret: bool = False):
    """feat: (X, NF) user features; x0: (X, 2) normalized warm starts.
    edge_tuple: tuple of (name, value) edge constants.
    Returns (x*: (X, 2), U*: (X,))."""
    ep = dict(edge_tuple)
    X = feat.shape[0]
    xb = min(user_block, max(X, 8))
    nb = pl.cdiv(X, xb)
    kernel = functools.partial(_ligd_kernel, iters=iters, lr=lr, ep=ep)
    x, u = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((xb, NF), lambda i: (i, 0)),
            pl.BlockSpec((xb, 2), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((xb, 2), lambda i: (i, 0)),
            pl.BlockSpec((xb, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((X, 2), jnp.float32),
            jax.ShapeDtypeStruct((X, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="mcsa_ligd_step",
    )(feat, x0)
    return x, u[:, 0]


# ---------------------------------------------------------------------------
# Fused whole-sweep kernels.  The split loop is UNROLLED over the static
# prefix tables (sweep_tables(profile)), so each split's (f_l, f_e, w,
# offloaded) is a compile-time constant; per-user/per-edge parameters come
# from the (NF_SWEEP, xb) feature block.  Step arithmetic is ref.py's.
# ---------------------------------------------------------------------------
SUBLANES = 8                      # f32 tile height: users fill (8, lanes)


def _sweep_kernel(feat_ref, x0_ref, u_ref, xB_ref, xr_ref, it_ref, best_ref,
                  zeros_ref, *, tables, lr, eps, max_iters, chunk, warm_start,
                  init, joint):
    # Every per-user quantity is one (SUBLANES, lanes) tile.  A (1, lanes)
    # row also compiles but fills one sublane of each vreg: on a v5e it
    # ran 2.4x (Li-GD) / 4.2x (MLi-GD) slower at 100k users.
    fr = {name: feat_ref[i].astype(jnp.float32)
          for i, name in enumerate(SWEEP_FIELDS)}
    x = tuple(x0_ref[i] for i in range(x0_ref.shape[0]))
    zeros_ref[...] = jnp.zeros(zeros_ref.shape, jnp.float32)
    zeros = zeros_ref[...]

    u_best = jnp.full_like(x[0], jnp.inf)
    s_best = jnp.zeros_like(x[0])
    x_best = x
    for s, tab in enumerate(tables):
        if not warm_start:
            x = _init_x(fr, init)
        x, u, it = _layer_solve(fr, x, zeros, tab, lr=lr, eps=eps,
                                max_iters=max_iters, chunk=chunk, joint=joint)
        u_ref[s] = u
        xB_ref[s] = x[0]
        xr_ref[s] = x[1]
        it_ref[s] = it
        better = u < u_best                            # strict: first min
        u_best = jnp.where(better, u, u_best)
        s_best = jnp.where(better, jnp.float32(s), s_best)
        x_best = tuple(jnp.where(better, a, b) for a, b in zip(x, x_best))

    for i, v in enumerate((s_best, u_best, *x_best)):
        best_ref[i] = v


@functools.partial(jax.jit, static_argnames=(
    "tables", "lr", "eps", "max_iters", "chunk", "warm_start", "init",
    "joint", "user_block", "interpret"))
def sweep_tpu(feat, x0, *, tables, lr=0.15, eps=1e-5, max_iters=400,
              chunk=16, warm_start=True, init=(0.5, 0.5), joint=False,
              user_block=2048, interpret=False):
    """Fused whole-sweep solve.  feat: (NF_SWEEP, X); x0: (K, X) with
    K = 2 (Li-GD) or 4 (MLi-GD joint).  Returns per-layer (M1, X) arrays
    (U, xB, xr, iters) plus a (2+K, X) best block
    [s*, U*, x*_components...] from the in-kernel argmin.

    Users are folded row-major onto (SUBLANES, xb/SUBLANES) tiles per
    block, so the chip needs xb a multiple of SUBLANES·128; a block never
    exceeds X rounded up to that size."""
    X = feat.shape[1]
    K = x0.shape[0]
    M1 = len(tables)
    xb = min(user_block, -(-X // (SUBLANES * 128)) * SUBLANES * 128)
    lanes = xb // SUBLANES
    nb = pl.cdiv(X, xb)
    # Pad a ragged final block with replicas of lane 0: garbage pad lanes
    # would never satisfy a stopping rule (NaN comparisons are False) and
    # pin that block's masked loop at max_iters; a real lane's replica
    # converges with it.
    Xp = nb * xb
    if Xp != X:
        feat = jnp.concatenate(
            [feat, jnp.broadcast_to(feat[:, :1], (feat.shape[0], Xp - X))],
            axis=1)
        x0 = jnp.concatenate(
            [x0, jnp.broadcast_to(x0[:, :1], (K, Xp - X))], axis=1)
    tile = lambda a: a.reshape(a.shape[0], Xp // lanes, lanes)
    kernel = functools.partial(
        _sweep_kernel, tables=tables, lr=lr, eps=eps, max_iters=max_iters,
        chunk=chunk, warm_start=warm_start, init=init, joint=joint)
    spec = lambda rows: pl.BlockSpec((rows, SUBLANES, lanes),
                                     lambda i: (0, i, 0))
    u, xB, xr, it, best = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[spec(NF_SWEEP), spec(K)],
        out_specs=[spec(M1)] * 4 + [spec(2 + K)],
        out_shape=[jax.ShapeDtypeStruct((M1, Xp // lanes, lanes),
                                        jnp.float32)] * 4
        + [jax.ShapeDtypeStruct((2 + K, Xp // lanes, lanes), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((SUBLANES, lanes), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="mcsa_mligd_sweep" if joint else "mcsa_ligd_sweep",
    )(tile(feat), tile(x0.astype(jnp.float32)))
    return tuple(a.reshape(a.shape[0], Xp)[:, :X]
                 for a in (u, xB, xr, it, best))


def ligd_sweep_tpu(feat, x0, *, tables, **kw):
    return sweep_tpu(feat, x0, tables=tables, joint=False, **kw)


def mligd_sweep_tpu(feat, x0, *, tables, init=(0.5, 0.5, 0.5, 0.5), **kw):
    return sweep_tpu(feat, x0, tables=tables, joint=True, init=init, **kw)


def pack_features(f_l, f_e, w, m, offl, dev: dict) -> jnp.ndarray:
    """Assemble the (X, NF) feature matrix from batched device dicts."""
    e_per_flop = dev["xi"] * dev["c_dev"] ** 2 * dev["phi"]
    c1 = dev["p_tx"] * dev["alpha"] * dev["g_fade"]
    cols = [f_l, f_e, w, m, offl, dev["c_dev"], e_per_flop, dev["p_tx"],
            c1, dev["hops"], dev["k_rounds"], dev["t_ag"], dev["w_T"],
            dev["w_E"], dev["w_C"], jnp.zeros_like(f_l)]
    return jnp.stack([jnp.broadcast_to(c, f_l.shape) for c in cols], -1)


def edge_tuple_of(edge: dict) -> tuple:
    """Hashable edge constants for the kernel (per-server, static)."""
    c1 = None
    keys = ("B_min", "B_max", "r_min", "r_max", "lam_a", "c_min",
            "rho_min", "rho_B", "gamma_B", "B0", "B_backhaul", "N0")
    return tuple((k, float(edge[k])) for k in keys)
