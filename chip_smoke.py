#!/usr/bin/env python3
"""Smoke run of the MCSA system on one TPU chip, through its user entry points.

Phases, in one process, in order:

  plan    Session(megafleet_100k).run(): 100 000 users, the static Li-GD
          plan plus 5 async MLi-GD handoff steps.  Every plan is finite,
          no user sits on a missing server, and the compiled solves carry
          the fused-sweep Pallas kernels (``mcsa_ligd_sweep`` /
          ``mcsa_mligd_sweep``), not the reference.
  parity  the kernel against the masked-JAX reference on the whole static
          batch (and on the largest handoff batch), and the fused solve
          against ``solver="autodiff"`` on the first 1 024 rows.
  engine  InferenceEngine on starcoder2-3b at its published widths (bf16,
          random weights from PRNGKey(0)), 4 slots x 1 024 cache, six
          seeded prompts; every emitted token must be the argmax of a
          cache-free teacher-forced prefill wherever that prefill's top-2
          logit margin exceeds 5e-2 of its logit RMS.
  serve   ``repro.launch.serve`` on serve_chaos_k3 (scripted kill,
          failover, zero requests lost).

Each phase prints one line with its wall time (a smoke timing, compile
included: not a benchmark number) and its key numbers.  Any failed check
raises; the exit code is then non-zero.  The last line of a passing run
is ``{"ok": true, "device": {...}}``.  Without a TPU the script exits
non-zero before the first phase.

    python chip_smoke.py
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SPLIT_AGREE_MIN = 0.999          # share of rows whose split must agree
U_REL_GAP_MAX = 1e-4             # relative U gap where splits agree
AUTODIFF_ROWS = 1024
MARGIN_FRAC = 5e-2               # token check: top-2 margin / logit RMS


class SmokeFailure(RuntimeError):
    pass


def check(ok, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def report(phase: str, t0: float, **numbers) -> None:
    items = " ".join(f"{k}={v}" for k, v in numbers.items())
    print(f"[{phase}] smoke_wall_s={time.perf_counter() - t0:.2f} {items}",
          flush=True)


def require_tpu():
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke needs a TPU; JAX found "
                         f"{dev.platform!r} ({dev.device_kind})")
    return dev


# ---------------------------------------------------------------------------
# plan
# ---------------------------------------------------------------------------
def _record_solves():
    """Wrap the planner's two jit-cached solve entry points so the phase
    can inspect exactly the batches (and compiled programs) it used."""
    from repro.core import planner
    seen = {"ligd": [], "mligd": []}
    for name, key in (("solve_ligd_batch_jit", "ligd"),
                      ("solve_mligd_batch_jit", "mligd")):
        real = getattr(planner, name)

        def spy(*args, _real=real, _key=key):
            seen[_key].append(args)
            return _real(*args)

        setattr(planner, name, spy)
    return seen


def _jitted_solve(kind: str, args):
    """The planner's own cached jitted solve for these args."""
    from repro.core import ligd, mligd
    profile, cfg = args[0], args[-1]
    cache = ligd._PROFILE_CACHE if kind == "ligd" else mligd._CACHE
    for key, fn in cache.items():
        if key[0] == profile.fingerprint and key[1] == cfg:
            return fn
    raise SmokeFailure(f"no cached {kind} solve for the planner's profile")


def compiled_hlo(kind: str, args) -> str:
    """Compiled HLO of the planner's solve for ``args``."""
    return _jitted_solve(kind, args).lower(*args[1:-1]).compile().as_text()


def phase_plan(scenario):
    from repro.api import Session
    t0 = time.perf_counter()
    seen = _record_solves()
    sess = Session(scenario)
    sess.run()
    fleet = sess.fleet
    X = len(fleet)
    check(X == scenario.num_users, f"fleet holds {X} of "
          f"{scenario.num_users} users")
    finite = np.ones(X, bool)
    for col in ("B", "r", "U", "T", "E", "C"):
        finite &= np.isfinite(getattr(fleet, col))
    check(finite.all(), f"{int((~finite).sum())} users hold a non-finite plan")
    Z = sess.topo.num_servers
    up = sess.topo.server_available()
    offl = fleet.split < sess.profile.num_layers
    valid = (fleet.server >= 0) & (fleet.server < Z)
    check(valid.all(), f"{int((~valid).sum())} users on a server id "
          f"outside [0, {Z})")
    check(up[fleet.server[offl]].all(), "an offloading user sits on a "
          "down server")
    check(seen["ligd"], "the planner never called the Li-GD solve")
    check(seen["mligd"], "the planner never called the MLi-GD solve")
    for kind, name in (("ligd", "mcsa_ligd_sweep"),
                       ("mligd", "mcsa_mligd_sweep")):
        hlo = compiled_hlo(kind, seen[kind][0])
        check("tpu_custom_call" in hlo and name in hlo,
              f"compiled {kind} solve does not call the {name} Pallas kernel")
    report("plan", t0, users=X, servers=Z, steps=sess.steps_taken,
           handoffs=sess.total_handoffs, offloaded=int(offl.sum()),
           ligd_solves=len(seen["ligd"]), mligd_solves=len(seen["mligd"]),
           mean_U=float(np.mean(fleet.U)), mean_T=float(np.mean(fleet.T)),
           kernels="mcsa_ligd_sweep,mcsa_mligd_sweep",
           session_s={k: round(v, 3) for k, v in sess.timings.items()})
    return seen


# ---------------------------------------------------------------------------
# parity
# ---------------------------------------------------------------------------
def agreement(split_a, u_a, split_b, u_b) -> dict:
    split_a, split_b = np.asarray(split_a), np.asarray(split_b)
    u_a = np.asarray(u_a, np.float64)
    u_b = np.asarray(u_b, np.float64)
    same = split_a == split_b
    gap = np.abs(u_a - u_b) / np.maximum(np.abs(u_b), 1e-30)
    return {"rows": int(len(same)),
            "split_agree": float(same.mean()),
            "max_rel_U_gap": float(gap[same].max()) if same.any() else None,
            "mean_U": [float(u_a.mean()), float(u_b.mean())]}


def check_agreement(name: str, a: dict) -> None:
    check(a["split_agree"] >= SPLIT_AGREE_MIN,
          f"{name}: splits agree on {a['split_agree']:.6f} of rows "
          f"(< {SPLIT_AGREE_MIN})")
    check(a["max_rel_U_gap"] is not None
          and a["max_rel_U_gap"] <= U_REL_GAP_MAX,
          f"{name}: relative U gap {a['max_rel_U_gap']} > {U_REL_GAP_MAX}")


def _rows(tree, n: int):
    import jax
    return jax.tree.map(lambda a: a[:n] if np.ndim(a) else a, tree)


def parity_ligd(args) -> dict:
    import dataclasses

    import jax
    import jax.numpy as jnp

    from repro.core.ligd import solve_ligd_batch
    from repro.kernels.ligd_step import (ligd_sweep, ligd_sweep_ref,
                                         pack_sweep_features, sweep_tables)
    profile, devs, edge, cfg = args
    X = devs["c_dev"].shape[0]
    feat = pack_sweep_features(devs, edge, profile.result_bits, X)
    x0 = jnp.broadcast_to(jnp.asarray(cfg.init, jnp.float32)[:, None], (2, X))
    tables = sweep_tables(profile)
    kw = dict(lr=cfg.lr, eps=cfg.eps, max_iters=cfg.max_iters,
              chunk=cfg.chunk, warm_start=cfg.warm_start, init=cfg.init)
    kern = jax.jit(lambda f, x: ligd_sweep(f, x, tables, **kw))(feat, x0)
    ref = jax.jit(lambda f, x: ligd_sweep_ref(f, x, tables, **kw))(feat, x0)
    out = {"kernel_vs_ref": agreement(kern.best_s, kern.best_u,
                                      ref[3], ref[5])}
    n = min(AUTODIFF_ROWS, X)
    fused = _jitted_solve("ligd", args)(devs, edge)
    auto_cfg = dataclasses.replace(cfg, solver="autodiff")
    auto = jax.jit(lambda d, e: solve_ligd_batch(profile, d, e, auto_cfg))(
        _rows(devs, n), _rows(edge, n))
    out["fused_vs_autodiff"] = agreement(
        np.asarray(fused.split)[:n], np.asarray(fused.U)[:n],
        auto.split, auto.U)
    return out


def parity_mligd(args) -> dict:
    import dataclasses

    import jax
    import jax.numpy as jnp

    from repro.core.mligd import solve_mligd_batch
    from repro.kernels.ligd_step import (mligd_sweep, mligd_sweep_ref,
                                         pack_sweep_features, sweep_tables)
    profile, devs, edge, origs, hops_back, cfg = args
    X = devs["c_dev"].shape[0]
    feat = pack_sweep_features(devs, edge, profile.result_bits, X,
                               orig=origs, hops_back=hops_back)
    init4 = (*cfg.init, 0.5, 0.5)
    x0 = jnp.broadcast_to(jnp.asarray(init4, jnp.float32)[:, None], (4, X))
    tables = sweep_tables(profile)
    kw = dict(lr=cfg.lr, eps=cfg.eps, max_iters=cfg.max_iters,
              chunk=cfg.chunk, warm_start=cfg.warm_start, init=init4)
    kern = jax.jit(lambda f, x: mligd_sweep(f, x, tables, **kw))(feat, x0)
    ref = jax.jit(lambda f, x: mligd_sweep_ref(f, x, tables, **kw))(feat, x0)
    out = {"kernel_vs_ref": agreement(kern.best_s, kern.best_u,
                                      ref[3], ref[5])}
    n = min(AUTODIFF_ROWS, X)
    fused = _jitted_solve("mligd", args)(devs, edge, origs, hops_back)
    auto_cfg = dataclasses.replace(cfg, solver="autodiff")
    auto = jax.jit(lambda d, e, o, h: solve_mligd_batch(
        profile, d, e, o, h, auto_cfg))(
        _rows(devs, n), _rows(edge, n), _rows(origs, n), _rows(hops_back, n))
    out["fused_vs_autodiff"] = agreement(
        np.asarray(fused.split)[:n], np.asarray(fused.U)[:n],
        auto.split, auto.U)
    return out


def phase_parity(seen) -> None:
    t0 = time.perf_counter()
    # the static plan, and the largest handoff batch the session solved
    lig = parity_ligd(seen["ligd"][0])
    mlig = parity_mligd(max(seen["mligd"],
                            key=lambda a: a[1]["c_dev"].shape[0]))
    for solver, res in (("ligd", lig), ("mligd", mlig)):
        for pair, a in res.items():
            check_agreement(f"{solver} {pair}", a)
    report("parity", t0, ligd=json.dumps(lig), mligd=json.dumps(mlig))


# ---------------------------------------------------------------------------
# engine
# ---------------------------------------------------------------------------
def phase_engine(cfg, *, slots: int, cache_len: int, prompt_lens,
                 max_new: int, seed: int = 0) -> None:
    import jax
    import jax.numpy as jnp

    from repro.models import transformer as tfm
    from repro.runtime.meshenv import CPU_ENV
    from repro.serving.engine import InferenceEngine

    t0 = time.perf_counter()
    params, _ = tfm.init_lm(cfg, jax.random.PRNGKey(0), CPU_ENV)
    jax.block_until_ready(params)
    n_params = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))
    t_init = time.perf_counter() - t0

    eng = InferenceEngine(cfg, params, env=CPU_ENV, slots=slots,
                          cache_len=cache_len)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, cfg.vocab_size, n).astype(np.int32)
               for n in prompt_lens]
    rids = [eng.submit(p, max_new) for p in prompts]
    t1 = time.perf_counter()
    outs = eng.run_to_completion()
    t_serve = time.perf_counter() - t1

    # cache-free recompute: one teacher-forced prefill per request over
    # prompt + produced tokens, right-padded to one length (causal, so
    # padding never reaches a checked position)
    L = cache_len
    recompute = jax.jit(lambda p, toks: tfm.prefill(
        cfg, p, CPU_ENV, {"tokens": toks}, cache_len=L,
        all_positions=True)[0][0, :, :cfg.vocab_size].astype(jnp.float32))
    checked = mismatched = 0
    max_gap = 0.0
    for rid, prompt in zip(rids, prompts):
        out = np.asarray(outs[rid])
        check(len(out) == max_new, f"request {rid} emitted {len(out)} "
              f"of {max_new} tokens")
        seq = np.concatenate([prompt, out[:-1]])
        check(len(seq) <= L, f"request {rid} longer than the cache")
        toks = np.zeros((1, L), np.int32)
        toks[0, :len(seq)] = seq
        logits = np.asarray(recompute(params, jnp.asarray(toks)))
        rows = logits[len(prompt) - 1:len(seq)]          # predicts out[j]
        check(np.isfinite(rows).all(), f"request {rid}: non-finite logits")
        top2 = np.sort(rows, axis=-1)[:, -2:]
        rms = np.sqrt(np.mean(rows * rows, axis=-1))
        margin = top2[:, 1] - top2[:, 0]
        chosen = rows[np.arange(len(out)), out]
        gap = (top2[:, 1] - chosen) / rms
        max_gap = max(max_gap, float(gap.max()))
        decided = margin > MARGIN_FRAC * rms
        wrong = decided & (rows.argmax(-1) != out)
        checked += int(decided.sum())
        mismatched += int((rows.argmax(-1) != out).sum())
        check(not wrong.any(), f"request {rid}: token(s) "
              f"{np.flatnonzero(wrong).tolist()} disagree with the "
              f"cache-free recompute beyond the top-2 margin")
    stats = jax.devices()[0].memory_stats() or {}
    report("engine", t0, arch=cfg.name, layers=cfg.num_layers,
           d_model=cfg.d_model, params=n_params, dtype=cfg.dtype,
           init_s=round(t_init, 2), serve_s=round(t_serve, 2),
           requests=len(rids), tokens=len(rids) * max_new,
           tokens_checked=checked, tokens_differing_in_ties=mismatched,
           max_rel_logit_gap=max_gap,
           peak_bytes_in_use=stats.get("peak_bytes_in_use"))


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------
def phase_serve(argv) -> None:
    from repro.launch import serve
    t0 = time.perf_counter()
    rc = serve.main(list(argv))
    check(rc == 0, f"repro.launch.serve returned {rc}")
    report("serve", t0, scenario=argv[-1], lost=0)


def main(argv=None) -> int:
    from repro.launch.compile_cache import use_compile_cache
    cache = use_compile_cache()
    dev = require_tpu()
    import jax

    from repro.api import get_scenario
    from repro.configs import get_config
    print(f"[device] {dev.platform} {dev.device_kind} x{len(jax.devices())} "
          f"jax {jax.__version__} compile_cache={cache}", flush=True)

    seen = phase_plan(get_scenario("megafleet_100k"))
    phase_parity(seen)
    rng = np.random.default_rng(1)
    lens = np.sort(rng.integers(17, 701, 6))
    lens[0], lens[-1] = 17, 700
    phase_engine(get_config("starcoder2-3b"), slots=4, cache_len=1024,
                 prompt_lens=lens.tolist(), max_new=16)
    phase_serve(["--scenario", "serve_chaos_k3"])

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
