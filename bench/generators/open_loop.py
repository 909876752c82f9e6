"""Generator of the serving cells' traffic: open-loop arrivals into the edge engine.

Traffic parameters (``bench/traffic/<name>.json``, kind ``open_loop``):
``rate_per_s`` (Poisson arrivals), ``prompt_len`` and ``output_len``
(discretized lognormals given by ``median`` and ``sigma``, clipped to
``[min, max]``), ``mix_seed``, ``first_token_wait_s`` and the size of the
check (``check_requests``, ``check_tokens``, and ``check_block``, the
requests the reference takes at a time).  Every seed gets the same
schedule, drawn from ``mix_seed``: arrival times, prompt lengths and
answer lengths, in one order.  (The window cuts the answers still
running at its end, so a seed that reordered them would change the
tokens a window holds by about a tenth.)  ``--seed`` draws the tokens,
uniform over the vocabulary, and the weights.  Decoding is greedy.

The configuration decides the architecture.  Its ``model`` group names
the program's registered model (``model_id``) and the plain reference
module (``reference``: ``bench/reference/<module>.py``), which supplies
the forward pass, the weight-draw rule and the operation counts; nothing
here names a layer or a leaf.  ``program_config`` holds the registered
model to the group, and applies the cuts the top-level ``reduced`` lists.

The entry is the engine every data-plane pool wraps,
``InferenceEngine.submit`` / ``.step``, driven in wall time from the
client's side.  Set-up makes the weights on the device from the seed (one
jitted call), builds the engine, and admits one request of every prompt
length the traffic can send into every slot, so that every prefill
length, slot write and the decode step are compiled or loaded from the
cache.  Each request is timed from its scheduled send time; arrivals stop
at the window's end, and the run then steps on (up to
``first_token_wait_s``) until every request due in the window has its
first token.  The check runs the float32 reference over a sample of
finished requests, the longest among them.
"""
from __future__ import annotations

import dataclasses
import math
import statistics
import time

import numpy as np

from bench.logit_gaps import logit_gaps

# keys of the model group that name things rather than state a field of
# the program's model
NAMING = ("kind", "model_id", "reference")


def program_config(cfg: dict):
    """The program's registered model (``model_id``), held to the
    configuration: every key of the ``model`` group but ``NAMING`` equals
    the registry's value, except a key the top-level ``reduced`` lists,
    which is applied (the chip's share of the published model: depth,
    experts held, a vocabulary slice); and every field the registry sets
    away from its default (``name`` and ``family`` aside) is stated.  So
    the reference sees every mechanism of the model the program runs."""
    from repro.configs import ModelConfig, get_config
    m = cfg["model"]
    if "reference" not in m:
        raise ValueError("the configuration's model group names no "
                         "`reference` module (bench/reference/<module>.py)")
    if "reduced" not in cfg:
        raise ValueError("the configuration states no top-level `reduced` "
                         "list")
    reg = get_config(m["model_id"])
    fields = {f.name: f for f in dataclasses.fields(ModelConfig)}
    stated = {k: tuple(v) if isinstance(v, list) else v
              for k, v in m.items() if k not in NAMING}
    cut = set(cfg["reduced"])
    faults = []
    if set(stated) - set(fields):
        faults.append(f"not fields of the program's model: "
                      f"{sorted(set(stated) - set(fields))}")
    if cut - set(stated):
        faults.append(f"in `reduced` but not stated: "
                      f"{sorted(cut - set(stated))}")
    departs = {k: (getattr(reg, k), v) for k, v in sorted(stated.items())
               if k in fields and k not in cut and getattr(reg, k) != v}
    if departs:
        faults.append(f"departing from the registry (registry, file) "
                      f"without being in `reduced`: {departs}")
    unstated = sorted(
        k for k, f in fields.items()
        if k not in ("name", "family") and k not in stated
        and (f.default is dataclasses.MISSING or getattr(reg, k) != f.default))
    if unstated:
        faults.append(f"set by the registry but not stated: {unstated}")
    if faults:
        raise ValueError(f"the program's {m['model_id']} departs from the "
                         f"configuration: " + "; ".join(faults))
    return dataclasses.replace(reg, **{k: stated[k] for k in cut})


def make_weights(mcfg, seed: int, rule: dict):
    """Every weight of the program's parameter tree, drawn on the device
    from the seed in one jitted call, in the served dtype, by the
    reference's ``rule`` (leaf name -> fan-in axes, or None): matrices
    normal with variance 1/fan_in, norm weights normal at 0.1 (they enter
    as 1 + w).  A leaf the rule does not name is an error."""
    import jax
    import jax.numpy as jnp
    from repro.models import transformer as tfm
    from repro.runtime.meshenv import CPU_ENV
    shapes = jax.eval_shape(
        lambda k: tfm.init_lm(mcfg, k, CPU_ENV)[0], jax.random.PRNGKey(0))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    unnamed = sorted({jax.tree_util.keystr(path) for path, _ in leaves
                      if path[-1].key not in rule})
    if unnamed:
        raise ValueError(f"the reference's weight rule names no leaf of "
                         f"{unnamed}")

    def gen(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for (path, sd), k in zip(leaves, keys):
            axes = rule[path[-1].key]
            z = jax.random.normal(k, sd.shape, jnp.float32)
            if axes is None:
                z = 0.1 * z
            else:
                z = z / math.sqrt(math.prod(sd.shape[a] for a in axes))
            out.append(z.astype(sd.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             seed >> 31)
    return jax.jit(gen)(key)


def _lengths(rng, spec: dict, n: int) -> np.ndarray:
    x = np.exp(rng.normal(math.log(spec["median"]), spec["sigma"], n))
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def schedule(tr: dict, seconds: float, seed: int, vocab: int):
    """(arrival times, prompts, output lengths) for one window."""
    n = int(math.ceil(tr["rate_per_s"] * seconds * 1.25)) + 8
    mix = np.random.default_rng(tr["mix_seed"])
    gaps = mix.exponential(1.0 / tr["rate_per_s"], n)
    p_len = _lengths(mix, tr["prompt_len"], n)
    o_len = _lengths(mix, tr["output_len"], n)
    rng = np.random.default_rng([seed, 3])
    prompts = [rng.integers(0, vocab, int(s)).astype(np.int32)
               for s in p_len]
    return np.cumsum(gaps), prompts, o_len


def warm(engine, tr: dict, vocab: int) -> None:
    """Admit a request of every prompt length into every slot, decode
    once, and release everything."""
    lens = list(range(tr["prompt_len"]["min"], tr["prompt_len"]["max"] + 1))
    n = max(engine.slots, len(lens))
    rng = np.random.default_rng(0)
    rids = [engine.submit(rng.integers(0, vocab, lens[i % len(lens)])
                          .astype(np.int32), 2) for i in range(n)]
    while engine.free_slots < engine.slots or engine._queue:
        engine.step()
    for rid in rids:
        engine.pop_result(rid)


def window(engine, arrivals, prompts, out_len, seconds: float,
           wait_s: float, spans=None):
    """Drive the open loop; returns what the client saw."""
    import jax
    n_due = int(np.searchsorted(arrivals, seconds))
    rid_of, sent, first, last = {}, {}, {}, {}
    gaps, tokens_in_window, late = [], 0, []
    waiting = set()
    step_s, step_info = [], []
    i = 0
    t0 = time.perf_counter()
    while True:
        now = time.perf_counter() - t0
        if now >= seconds and (not waiting or now >= seconds + wait_s):
            break
        while i < n_due and arrivals[i] <= now:
            rid = engine.submit(prompts[i], int(out_len[i]))
            rid_of[i] = rid
            sent[rid] = arrivals[i]
            late.append(now - arrivals[i])
            waiting.add(rid)
            i += 1
        busy = engine._queue or engine.state.active.any()
        if not busy:
            nxt = arrivals[i] if i < n_due else seconds
            time.sleep(max(0.0, min(nxt, seconds) - now))
            continue
        if spans is not None:
            step_info.append((int(engine.state.active.sum()),
                              [int(p) for p, a in zip(engine.state.pos,
                                                      engine.state.active)
                               if a], [len(r.tokens) for r in
                                       engine._queue[:engine.free_slots]]))
        ts = time.perf_counter()
        if spans is not None:
            with jax.profiler.TraceAnnotation("bench.engine_step"):
                emitted = engine.step()
        else:
            emitted = engine.step()
        tn = time.perf_counter() - t0
        step_s.append(tn - (ts - t0))
        for rid in [r for r in waiting if r in engine.requests]:
            first[rid] = last[rid] = tn
            waiting.discard(rid)
            if tn <= seconds:
                tokens_in_window += 1
        for rid, _tok in emitted:
            if tn <= seconds:
                gaps.append(tn - last[rid])
                tokens_in_window += 1
            last[rid] = tn
    t_end = time.perf_counter() - t0
    ttft = [first.get(r, t_end) - sent[r] for r in sent]
    return {"rid_of": rid_of, "n_due": n_due, "ttft": ttft, "itl": gaps,
            "tokens": tokens_in_window, "failed": len(waiting),
            "late": late, "step_s": step_s, "step_info": step_info}


def p95(xs):
    return statistics.quantiles(xs, n=20, method="inclusive")[18]


def run(ctx) -> dict:
    import jax
    from repro.runtime.meshenv import CPU_ENV
    from repro.serving.engine import InferenceEngine

    cfg, tr, seed = ctx["config"], ctx["traffic"], ctx["seed"]
    log = ctx["log"]
    m = cfg["model"]
    mcfg = program_config(cfg)
    ref = ctx["load_reference"](m["reference"])
    rule = ref.weight_rule(m)
    t0 = time.perf_counter()
    params = make_weights(mcfg, seed, rule)
    jax.block_until_ready(params)
    t_w = time.perf_counter() - t0
    engine = InferenceEngine(mcfg, params, env=CPU_ENV,
                             slots=cfg["engine"]["slots"],
                             cache_len=cfg["engine"]["cache_len"])
    hook = ctx["hooks"].get("engine")
    if hook:
        hook(engine)
    warm(engine, tr, m["vocab_size"])
    arrivals, prompts, out_len = schedule(tr, ctx["seconds"], seed,
                                          m["vocab_size"])
    jax.block_until_ready(engine.state.caches)
    setup_s = ctx["setup_clock"]()
    log(f"setup: weights {t_w:.2f} s, engine and warm-up "
        f"{time.perf_counter() - t0 - t_w:.2f} s")

    tracing = ctx["trace_dir"] is not None
    if tracing:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(ctx["trace_dir"], profiler_options=opts)
    ctx["compiles"].begin_window()
    with jax.profiler.TraceAnnotation("bench.window"):
        seen = window(engine, arrivals, prompts, out_len, ctx["seconds"],
                      tr["first_token_wait_s"],
                      spans=True if tracing else None)
    ctx["compiles"].end_window()
    if tracing:
        jax.profiler.stop_trace()
    peak = ctx["memory_peak_bytes"]()
    log(f"window: {seen['n_due']} requests due, {seen['tokens']} tokens, "
        f"{len(seen['step_s'])} engine steps (longest "
        f"{max(seen['step_s'], default=0) * 1e3:.1f} ms), {seen['failed']} "
        f"without a first token; generator late p95 "
        f"{p95(seen['late']) * 1e3 if len(seen['late']) > 1 else 0:.3f} ms")

    # the sample to check: finished requests, the longest first, then
    # others drawn from the seed, up to check_tokens served tokens
    done = [(rid, engine.requests[rid]) for rid in seen["rid_of"].values()
            if rid in engine.requests and engine.requests[rid].done]
    rng = np.random.default_rng([seed, 4])
    sample = []
    if done:
        done.sort(key=lambda x: -len(x[1].out))
        rest = [done[j] for j in rng.permutation(len(done) - 1) + 1]
        total = 0
        for rid, req in [done[0]] + rest:
            if len(sample) >= tr["check_requests"] or \
                    total >= tr["check_tokens"]:
                break
            sample.append((np.asarray(req.tokens), np.asarray(req.out)))
            total += len(req.out)
    engine.state.caches = None
    del engine
    t1 = time.perf_counter()
    checks, control, readings = compare(cfg, params, sample, ctx["hooks"],
                                         int(tr["check_block"]), ref.forward)
    log(f"check: {len(sample)} requests against the reference in "
        f"{time.perf_counter() - t1:.2f} s")

    window_s = ctx["seconds"]
    out = {
        "end_to_end": {
            "output_tokens_per_s": seen["tokens"] / window_s,
            "ttft_p95_ms": p95(seen["ttft"]) * 1e3,
            "itl_p95_ms": p95(seen["itl"]) * 1e3,
            "setup_s": setup_s},
        "attempted": seen["n_due"], "failed": seen["failed"],
        "checks": checks, "memory_peak_bytes": peak,
        "facts": {"steps": len(seen["step_s"]), "step_s": seen["step_s"],
                  "step_info": seen["step_info"], "reference": ref},
    }
    out["readings"] = readings
    if control is not None:
        out["control"] = control
    return out


def gap_numbers(gaps: np.ndarray) -> dict:
    """The numbers a check can compare, from the per-token gaps."""
    if not len(gaps):
        return {"logit_gap_max": float("inf"), "logit_gap_p99": float("inf"),
                "token_mismatch_share": float("inf")}
    return {"logit_gap_max": float(np.max(gaps)),
            "logit_gap_p99": float(np.quantile(gaps, 0.99)),
            "token_mismatch_share": float(np.mean(gaps > 0))}


def compare(cfg, params, sample, hooks, block: int, forward):
    """How far each served token's logit lies below the best of the
    reference's ``forward``, over the sample (and the same for the
    control's first choices, when asked), ``block`` requests at a time so
    that the reference fits beside the weights; returns (the program's
    checks, the control's checks or None, every number read, the
    program's and the control's)."""
    import jax.numpy as jnp
    m, lim = cfg["model"], cfg["check"]
    L = cfg["engine"]["cache_len"]
    B = max(block, -(-len(sample) // block) * block)
    toks = np.zeros((B, L), np.int32)
    tgt = np.zeros((B, L), np.int32)
    valid = np.zeros((B, L), bool)
    n = 0
    for b, (prompt, out) in enumerate(sample):
        seq = np.concatenate([prompt, out[:-1]])
        toks[b, :len(seq)] = seq
        lo = len(prompt) - 1
        tgt[b, lo:lo + len(out)] = out
        valid[b, lo:lo + len(out)] = True
        n += len(out)

    def gaps(int8=False):
        out = [np.asarray(logit_gaps(
            forward, m, params, jnp.asarray(toks[i:i + block]),
            jnp.asarray(tgt[i:i + block]), jnp.asarray(valid[i:i + block]),
            int8=int8)) for i in range(0, B, block)]
        return np.concatenate(out)[valid]

    def judged(numbers):
        out = [{"name": "tokens_checked", "value": n, "limit": 1,
                "ok": n >= 1}]
        for name, v in numbers.items():
            if name in lim:
                out.append({"name": name, "value": v, "limit": lim[name],
                            "ok": bool(np.isfinite(v)) and v <= lim[name]})
        return out

    readings = {"program": gap_numbers(gaps())}
    control = None
    if hooks.get("control"):
        readings["control"] = gap_numbers(gaps(int8=True))
        control = judged(readings["control"])
    return judged(readings["program"]), control, readings
