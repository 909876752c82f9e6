"""Decoder-stack orchestration for all ten assigned architectures.

Key structural ideas:

* **Pattern-period scan.**  ``cfg.pattern`` is the repeating unit of layer
  types (e.g. gemma3 = 5×local + 1×global; recurrentgemma = rglru, rglru,
  local-attn).  Parameters for ``num_layers // len(pattern)`` "superblocks"
  are stacked and applied with one ``lax.scan`` whose body statically
  unrolls the pattern — compile time is O(pattern), not O(depth).  The
  ``num_layers % len(pattern)`` remainder layers run unrolled first
  (both gemma3 and recurrentgemma lead with local/recurrent layers).
* **Caches as scan ys.**  Decode threads KV caches / recurrent states
  through the same scan via xs→ys, so serve_step HLO is also O(pattern).
* **Sequence sharding.**  Between blocks the residual stream is sharded
  (batch→data, seq→model) — Megatron-style sequence parallelism; GSPMD
  inserts the all-gather/reduce-scatter pairs around TP matmuls.
* Params and caches carry parallel PartitionSpec trees; specs are the
  single source of truth consumed by the launcher's in/out_shardings.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import (ATTN_GLOBAL, ATTN_LOCAL, RGLRU, RWKV6,
                                ModelConfig)
from repro.runtime.meshenv import MeshEnv
from . import attention as attn_lib
from .layers import (apply_mlp, apply_rope, init_attention, init_mlp,
                     init_norm, rms_norm)
from .moe import apply_moe, init_moe
from .rglru import (apply_rglru_decode, apply_rglru_seq, init_rglru,
                    init_rglru_state)
from .rwkv import (apply_channel_mix, apply_time_mix, init_rwkv_channel_mix,
                   init_rwkv_state, init_rwkv_time_mix)
from .sharded_ops import (embed_lookup, fused_unembed_xent, padded_vocab,
                          sharded_argmax, unembed_logits)

Params = Dict[str, Any]
MOE_AUX_WEIGHT = 0.01


# ===========================================================================
# Init
# ===========================================================================
def init_block(cfg: ModelConfig, key, layer_type: str, env: MeshEnv, *,
               cross: bool = False) -> Tuple[Params, dict]:
    ks = jax.random.split(key, 6)
    p: Params = {}
    s: dict = {}
    p["ln1"], s["ln1"] = init_norm(cfg)
    if layer_type in (ATTN_GLOBAL, ATTN_LOCAL):
        p["mix"], s["mix"] = init_attention(cfg, ks[0], env)
    elif layer_type == RGLRU:
        p["mix"], s["mix"] = init_rglru(cfg, ks[0], env)
    elif layer_type == RWKV6:
        p["mix"], s["mix"] = init_rwkv_time_mix(cfg, ks[0], env)
    else:
        raise ValueError(layer_type)
    if cross:
        p["ln_cross"], s["ln_cross"] = init_norm(cfg)
        p["cross"], s["cross"] = init_attention(cfg, ks[1], env, cross=True)
    p["ln2"], s["ln2"] = init_norm(cfg)
    if layer_type == RWKV6:
        p["ffn"], s["ffn"] = init_rwkv_channel_mix(cfg, ks[2], env)
    elif cfg.num_experts:
        p["ffn"], s["ffn"] = init_moe(cfg, ks[2], env)
    else:
        p["ffn"], s["ffn"] = init_mlp(cfg, ks[2], env)
    return p, s


def _stack_init(cfg: ModelConfig, key, env: MeshEnv, n: int, layer_type: str,
                cross: bool) -> Tuple[Params, dict]:
    """Init ``n`` copies of a block, stacked on a leading axis."""
    keys = jax.random.split(key, n)
    p0, s0 = init_block(cfg, keys[0], layer_type, env, cross=cross)
    stacked = jax.vmap(
        lambda k: init_block(cfg, k, layer_type, env, cross=cross)[0])(keys)
    specs = jax.tree.map(lambda sp: P(None, *sp), s0,
                         is_leaf=lambda x: isinstance(x, P))
    return stacked, specs


def _init_stack(cfg: ModelConfig, key, env: MeshEnv, *, cross: bool
                ) -> Tuple[Params, dict]:
    """Params for one stack of cfg.num_layers blocks (pattern-period scan)."""
    types = cfg.layer_types()
    period = len(cfg.pattern)
    rem = cfg.num_layers % period
    n_sb = cfg.num_layers // period
    keys = jax.random.split(key, rem + period)
    tail_p, tail_s = [], []
    for i in range(rem):
        pi, si = init_block(cfg, keys[i], types[i], env, cross=cross)
        tail_p.append(pi)
        tail_s.append(si)
    scan_p, scan_s = [], []
    for j, lt in enumerate(cfg.pattern):
        pj, sj = _stack_init(cfg, keys[rem + j], env, n_sb, lt, cross)
        scan_p.append(pj)
        scan_s.append(sj)
    return ({"tail": tuple(tail_p), "scan": tuple(scan_p)},
            {"tail": tuple(tail_s), "scan": tuple(scan_s)})


def init_lm(cfg: ModelConfig, key, env: MeshEnv) -> Tuple[Params, dict]:
    """Full model params + PartitionSpec tree.

    The draws run under ``jax.jit``: eagerly, each weight (a whole stacked
    layer axis of it) would exist in f32 before its cast, a temporary as
    large as half the bf16 model at published widths.  Specs are static,
    so tracing recovers them."""
    specs: dict = {}

    def build(k):
        params, s = _init_lm(cfg, k, env)
        specs.update(s)
        return params

    return jax.jit(build)(key), specs


def _init_lm(cfg: ModelConfig, key, env: MeshEnv) -> Tuple[Params, dict]:
    dt = jnp.dtype(cfg.dtype)
    Vp = padded_vocab(cfg.vocab_size, env.tp)
    k_emb, k_stack, k_enc, k_un = jax.random.split(key, 4)
    scale = 1.0 / math.sqrt(cfg.d_model)
    embed = (jax.random.normal(k_emb, (Vp, cfg.d_model), jnp.float32)
             * scale).astype(dt)
    params: Params = {"embed": embed}
    specs: dict = {"embed": P("model", None)}
    params["final_norm"], specs["final_norm"] = init_norm(cfg)
    stack_p, stack_s = _init_stack(cfg, k_stack, env, cross=cfg.enc_dec)
    params["stack"] = stack_p
    specs["stack"] = stack_s
    if not cfg.tie_embeddings:
        unembed = (jax.random.normal(k_un, (cfg.d_model, Vp), jnp.float32)
                   * scale).astype(dt)
        params["unembed"] = unembed
        specs["unembed"] = P(None, "model")
    if cfg.enc_dec:
        enc_cfg = encoder_cfg(cfg)
        enc_p, enc_s = _init_stack(enc_cfg, k_enc, env, cross=False)
        params["encoder"] = enc_p
        specs["encoder"] = enc_s
        params["enc_norm"], specs["enc_norm"] = init_norm(cfg)
    return params, specs


def encoder_cfg(cfg: ModelConfig) -> ModelConfig:
    import dataclasses
    return dataclasses.replace(cfg, num_layers=cfg.num_enc_layers,
                               pattern=(ATTN_GLOBAL,), enc_dec=False)


# ===========================================================================
# Attention block application
# ===========================================================================
def _project_qkv(cfg: ModelConfig, p: Params, env: MeshEnv, x, positions,
                 layer_type: str, *, rope: bool = True):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    cp = env.context_parallel_attn
    if env.tp > 1 and not cp and q.shape[2] % env.tp == 0:
        # padded q heads always divide TP (layers.padded_heads)
        q = env.constrain(q, env.batch(), None, env.model(), None)
    elif env.tp > 1 and q.shape[1] % env.tp == 0:
        # context parallelism: q stays sequence-sharded; k/v (small for
        # GQA/MQA) all-gather to full length instead of the residual.
        q = env.constrain(q, env.batch(), env.model(), None, None)
        k = env.constrain(k, env.batch(), None, None, None)
        v = env.constrain(v, env.batch(), None, None, None)
    if cfg.qk_norm and "q_norm" in p:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if rope:
        theta = (cfg.rope_theta_local if layer_type == ATTN_LOCAL
                 else cfg.rope_theta)
        q = apply_rope(q, positions, theta)
        k = apply_rope(k, positions, theta)
    return q, k, v


def _to_ring(k: jnp.ndarray, W: int) -> jnp.ndarray:
    """(B, S, ...) -> (B, W, ...) ring-buffer layout (slot = pos % W)."""
    B, S = k.shape[:2]
    if S < W:
        pad = [(0, 0)] * k.ndim
        pad[1] = (0, W - S)
        return jnp.pad(k, pad)
    j = jnp.arange(W)
    src = (S - 1) - jnp.mod((S - 1) - j, W)
    return jnp.take(k, src, axis=1)


def apply_attention(cfg: ModelConfig, p: Params, env: MeshEnv, x, *,
                    layer_type: str, mode: str, positions,
                    cache: Optional[dict], cache_len: int = 0,
                    triangular: bool = False, static_loops: bool = False):
    """x: (B, S, d) normalized input -> (out (B,S,d), new_cache)."""
    B, S, d = x.shape
    Hq = p["wq"].shape[1]                # possibly TP-padded (layers.py)
    Hkv, hd = cfg.num_kv_heads, cfg.head_dim
    rep = Hq // Hkv
    W = cfg.window_size if layer_type == ATTN_LOCAL else 0

    if mode == "decode":
        assert cache is not None
        pos = positions                      # scalar int32 or (B,) vector
        pos_arr = jnp.asarray(pos)
        pos_bq = (pos_arr[:, None] if pos_arr.ndim == 1
                  else jnp.full((B, 1), pos_arr))
        q, k, v = _project_qkv(cfg, p, env, x, pos_bq, layer_type)
        quant = "k_scale" in cache
        if quant:
            k_store, k_sc = attn_lib.quantize_kv(k)
            v_store, v_sc = attn_lib.quantize_kv(v)
        else:
            k_store, v_store = k, v
        L = cache["k"].shape[1]
        slot = jnp.mod(pos_arr, L) if W else pos_arr
        if pos_arr.ndim == 1:
            # per-sequence positions (continuous batching): scatter rows.
            rows = jnp.arange(B)
            ck = cache["k"].at[rows, slot].set(
                k_store[:, 0].astype(cache["k"].dtype))
            cv = cache["v"].at[rows, slot].set(
                v_store[:, 0].astype(cache["v"].dtype))
            if quant:
                ks = cache["k_scale"].at[rows, slot].set(k_sc[:, 0])
                vs = cache["v_scale"].at[rows, slot].set(v_sc[:, 0])
        else:
            ck = jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k_store.astype(cache["k"].dtype), slot, axis=1)
            cv = jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v_store.astype(cache["v"].dtype), slot, axis=1)
            if quant:
                ks = jax.lax.dynamic_update_slice_in_dim(
                    cache["k_scale"], k_sc, slot, axis=1)
                vs = jax.lax.dynamic_update_slice_in_dim(
                    cache["v_scale"], v_sc, slot, axis=1)
        # grouped GQA decode: the cache is never widened to Hq heads.
        if quant:
            out = attn_lib.decode_attention(q, ck, cv, pos, window=W,
                                            k_scale=ks, v_scale=vs)
            new_cache = {"k": ck, "v": cv, "k_scale": ks, "v_scale": vs}
        else:
            out = attn_lib.decode_attention(q, ck, cv, pos, window=W)
            new_cache = {"k": ck, "v": cv}
    else:
        q, k, v = _project_qkv(cfg, p, env, x, positions, layer_type)
        if (env.tp > 1 and Hkv % env.tp == 0
                and not env.context_parallel_attn):
            k = env.constrain(k, env.batch(), None, env.model(), None)
            v = env.constrain(v, env.batch(), None, env.model(), None)
        causal = mode != "encode"
        # Local layers also go through chunked flash (bounded block-pair
        # live set); the triangular flag statically skips blocks outside
        # the causal/window band — see EXPERIMENTS.md §Perf.
        out = attn_lib.flash_attention(
            q, k, v, causal=causal, window=W,
            q_block=min(attn_lib.FLASH_Q_BLOCK, S),
            kv_block=min(attn_lib.FLASH_KV_BLOCK, S),
            triangular=triangular, static_loops=static_loops)
        new_cache = None
        if mode == "prefill":
            dt = jnp.dtype(cfg.dtype)
            quant = cache is not None and "k_scale" in cache
            if quant:
                k_store, k_sc = attn_lib.quantize_kv(k)
                v_store, v_sc = attn_lib.quantize_kv(v)
                dt = jnp.int8
            else:
                k_store, v_store = k, v
            if W:
                new_cache = {"k": _to_ring(k_store, W).astype(dt),
                             "v": _to_ring(v_store, W).astype(dt)}
                if quant:
                    new_cache["k_scale"] = _to_ring(k_sc[..., None], W)[..., 0]
                    new_cache["v_scale"] = _to_ring(v_sc[..., None], W)[..., 0]
            else:
                L = max(cache_len, S)
                new_cache = {
                    "k": jnp.zeros((B, L, Hkv, hd), dt).at[:, :S].set(
                        k_store.astype(dt)),
                    "v": jnp.zeros((B, L, Hkv, hd), dt).at[:, :S].set(
                        v_store.astype(dt)),
                }
                if quant:
                    new_cache["k_scale"] = jnp.zeros(
                        (B, L, Hkv), jnp.float32).at[:, :S].set(k_sc)
                    new_cache["v_scale"] = jnp.zeros(
                        (B, L, Hkv), jnp.float32).at[:, :S].set(v_sc)
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return out, new_cache


def apply_cross_attention(cfg: ModelConfig, p: Params, env: MeshEnv, x, *,
                          mode: str, kv_memory=None, cache=None):
    """Cross attention to encoder output.  kv_memory: (B, Ss, d) (train /
    prefill — k/v projected here); cache: precomputed {'k','v'} (decode)."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    if cache is None:
        k = jnp.einsum("bsd,dhk->bshk", kv_memory, p["wk"])
        v = jnp.einsum("bsd,dhk->bshk", kv_memory, p["wv"])
    else:
        k, v = cache["k"], cache["v"]
    out = attn_lib.flash_attention(q, k, v, causal=False,
                                   q_block=min(512, q.shape[1]),
                                   kv_block=min(1024, k.shape[1]))
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])


# ===========================================================================
# Block application
# ===========================================================================
def apply_block(cfg: ModelConfig, p: Params, env: MeshEnv, layer_type: str,
                h, *, mode: str, positions, cache=None, cache_len: int = 0,
                kv_memory=None, capacity_factor: float = 1.25,
                triangular: bool = False, static_loops: bool = False):
    """Residual block.  Returns (h, new_cache, aux_loss_scalar)."""
    aux = jnp.zeros((), jnp.float32)
    new_cache: dict = {}
    x = rms_norm(h, p["ln1"], cfg.norm_eps)
    if layer_type in (ATTN_GLOBAL, ATTN_LOCAL):
        out, mix_cache = apply_attention(
            cfg, p["mix"], env, x, layer_type=layer_type, mode=mode,
            positions=positions, cache=(cache or {}).get("mix"),
            cache_len=cache_len, triangular=triangular,
            static_loops=static_loops)
    elif layer_type == RGLRU:
        if mode == "decode":
            out, mix_cache = apply_rglru_decode(cfg, p["mix"], env, x,
                                                (cache or {})["mix"])
        else:
            out, mix_cache = apply_rglru_seq(
                cfg, p["mix"], env, x,
                (cache or {}).get("mix") if mode == "decode" else None)
            mix_cache = mix_cache if mode == "prefill" else None
    elif layer_type == RWKV6:
        st = (cache or {}).get("mix") if mode == "decode" else None
        out, mix_cache = apply_time_mix(cfg, p["mix"], env, x, st)
        mix_cache = mix_cache if mode in ("prefill", "decode") else None
    else:
        raise ValueError(layer_type)
    h = h + out
    if mix_cache is not None:
        new_cache["mix"] = mix_cache

    if "cross" in p:
        xc = rms_norm(h, p["ln_cross"], cfg.norm_eps)
        cross_cache = (cache or {}).get("cross") if mode == "decode" else None
        out = apply_cross_attention(cfg, p["cross"], env, xc, mode=mode,
                                    kv_memory=kv_memory, cache=cross_cache)
        h = h + out
        if mode == "prefill":
            new_cache["cross"] = {
                "k": jnp.einsum("bsd,dhk->bshk", kv_memory,
                                p["cross"]["wk"]).astype(jnp.dtype(cfg.dtype)),
                "v": jnp.einsum("bsd,dhk->bshk", kv_memory,
                                p["cross"]["wv"]).astype(jnp.dtype(cfg.dtype)),
            }
        elif mode == "decode":
            new_cache["cross"] = cache["cross"]

    x = rms_norm(h, p["ln2"], cfg.norm_eps)
    if layer_type == RWKV6:
        st = (cache or {}).get("ffn") if mode == "decode" else None
        out, ffn_cache = apply_channel_mix(cfg, p["ffn"], env, x, st)
        if mode in ("prefill", "decode"):
            new_cache["ffn"] = ffn_cache
    elif cfg.num_experts:
        out, aux_tok = apply_moe(cfg, p["ffn"], env, x,
                                 capacity_factor=capacity_factor)
        aux = jnp.mean(aux_tok)
    else:
        out = apply_mlp(p["ffn"], x)
    h = h + out

    # Sequence-parallel residual stream between blocks.
    S = h.shape[1]
    if mode != "decode" and env.tp > 1 and S % env.tp == 0:
        h = env.constrain(h, env.batch(), env.model(), None)
    else:
        h = env.constrain(h, env.batch(), None, None)
    return h, (new_cache or None), aux


# ===========================================================================
# Stack application (tail unrolled + pattern-period scan)
# ===========================================================================
def apply_stack(cfg: ModelConfig, stack: Params, env: MeshEnv, h, *,
                mode: str, positions, caches=None, cache_len: int = 0,
                kv_memory=None, remat: bool = False,
                capacity_factor: float = 1.25, triangular: bool = False,
                pattern: Optional[Tuple[str, ...]] = None,
                unroll: bool = False):
    """``unroll=True`` replaces the superblock ``lax.scan`` with a python
    loop (identical math/shardings).  HLO grows O(depth) but every op is
    visible exactly once per execution — required for exact
    ``cost_analysis()`` in the dry-run (XLA's cost model does not multiply
    while-loop bodies by trip count)."""
    pattern = pattern or cfg.pattern
    types = cfg.layer_types() if pattern == cfg.pattern else pattern
    period = len(pattern)
    rem = (cfg.num_layers % period) if pattern == cfg.pattern else 0
    with_cache = caches is not None
    aux_total = jnp.zeros((), jnp.float32)
    new_tail = []
    for i in range(rem):
        c = caches["tail"][i] if with_cache else None
        h, nc, aux = apply_block(cfg, stack["tail"][i], env, types[i], h,
                                 mode=mode, positions=positions, cache=c,
                                 cache_len=cache_len, kv_memory=kv_memory,
                                 capacity_factor=capacity_factor,
                                 triangular=triangular, static_loops=unroll)
        new_tail.append(nc)
        aux_total = aux_total + aux

    def body(carry, xs):
        h, aux = carry
        if with_cache:
            p_slice, c_slice = xs
        else:
            p_slice, c_slice = xs, None
        new_cs = []
        for j, lt in enumerate(pattern):
            c = c_slice[j] if with_cache else None
            h, nc, a = apply_block(cfg, p_slice[j], env, lt, h, mode=mode,
                                   positions=positions, cache=c,
                                   cache_len=cache_len, kv_memory=kv_memory,
                                   capacity_factor=capacity_factor,
                                   triangular=triangular,
                                   static_loops=unroll)
            new_cs.append(nc)
            aux = aux + a
        return (h, aux), (tuple(new_cs) if any(
            c is not None for c in new_cs) else None)

    if remat:
        body = jax.checkpoint(body)
    xs = (stack["scan"], caches["scan"]) if with_cache else stack["scan"]
    if unroll:
        n_sb = cfg.num_layers // period
        carry = (h, aux_total)
        ys = []
        for i in range(n_sb):
            xi = jax.tree.map(lambda x: x[i], xs)
            carry, y = body(carry, xi)
            ys.append(y)
        (h, aux_total2) = carry
        new_scan = None
        if with_cache and ys and ys[0] is not None:
            new_scan = jax.tree.map(lambda *zs: jnp.stack(zs), *ys)
    else:
        (h, aux_total2), new_scan = jax.lax.scan(body, (h, aux_total), xs)
    new_caches = None
    if with_cache:
        new_caches = {"tail": tuple(new_tail), "scan": new_scan}
    return h, new_caches, aux_total2


# ===========================================================================
# Caches
# ===========================================================================
def _kv_spec(env: MeshEnv, batch: int, L: int, Hkv: int) -> P:
    """KV-cache sharding for a (B, L, Hkv, hd) tensor.

    Preference order over the model axis:
      1. heads  — classic TP decode: each shard owns whole heads, attention
         needs no cross-shard reduction (moonshot/gemma3/seamless, kv=16);
      2. sequence — context parallelism: when Hkv doesn't divide tp the
         cache length is sharded instead (yi/qwen3/granite kv=8,
         starcoder2/internvl2 kv=2); GSPMD inserts the online-softmax
         reductions;
      3. replicated (tiny caches only).
    The batch dim is sharded over the data axes when divisible."""
    b_ax = env.batch() if (env.dp > 1 and batch % env.dp == 0) else None
    if env.tp > 1 and Hkv % env.tp == 0:
        return P(b_ax, None, "model", None)
    if env.tp > 1 and L % env.tp == 0:
        if b_ax is None and env.dp > 1 and L % (env.dp * env.tp) == 0:
            # batch too small to shard (long_500k B=1): spread the context
            # over every chip.
            return P(None, tuple(env.batch_axes) + ("model",), None, None)
        return P(b_ax, "model", None, None)
    return P(b_ax, None, None, None)


def init_layer_cache(cfg: ModelConfig, env: MeshEnv, layer_type: str,
                     batch: int, cache_len: int, cross_len: int = 0,
                     kv_quant: bool = False):
    """Zero cache + spec for one layer.  ``kv_quant``: int8 KV codes +
    per-row f32 scales (§Perf: halves decode cache traffic/footprint)."""
    dt = jnp.dtype(cfg.dtype)
    Hkv, hd = cfg.num_kv_heads, cfg.head_dim
    b_ax = env.batch() if (env.dp > 1 and batch % env.dp == 0) else None
    c: dict = {}
    s: dict = {}
    if layer_type in (ATTN_GLOBAL, ATTN_LOCAL):
        L = min(cfg.window_size, cache_len) if layer_type == ATTN_LOCAL \
            else cache_len
        sp = _kv_spec(env, batch, L, Hkv)
        kv_dt = jnp.int8 if kv_quant else dt
        c["mix"] = {"k": jnp.zeros((batch, L, Hkv, hd), kv_dt),
                    "v": jnp.zeros((batch, L, Hkv, hd), kv_dt)}
        s["mix"] = {"k": sp, "v": sp}
        if kv_quant:
            sc_sp = P(*sp[:3])
            c["mix"]["k_scale"] = jnp.zeros((batch, L, Hkv), jnp.float32)
            c["mix"]["v_scale"] = jnp.zeros((batch, L, Hkv), jnp.float32)
            s["mix"]["k_scale"] = sc_sp
            s["mix"]["v_scale"] = sc_sp
    elif layer_type == RGLRU:
        rnn_ax = "model" if (env.tp > 1 and cfg.d_rnn % env.tp == 0) else None
        c["mix"] = init_rglru_state(cfg, batch)
        s["mix"] = {"h": P(b_ax, rnn_ax),
                    "conv": P(b_ax, None, rnn_ax)}
    elif layer_type == RWKV6:
        st = init_rwkv_state(cfg, batch)
        H = cfg.rwkv_num_heads
        h_ax = "model" if (env.tp > 1 and H % env.tp == 0) else None
        c["mix"] = {"s": st["s"], "tm": st["tm"]}
        c["ffn"] = {"cm": st["cm"]}
        s["mix"] = {"s": P(b_ax, h_ax, None, None),
                    "tm": P(b_ax, None)}
        s["ffn"] = {"cm": P(b_ax, None)}
    if cfg.enc_dec and cross_len:
        sp = _kv_spec(env, batch, cross_len, Hkv)
        c["cross"] = {"k": jnp.zeros((batch, cross_len, Hkv, hd), dt),
                      "v": jnp.zeros((batch, cross_len, Hkv, hd), dt)}
        s["cross"] = {"k": sp, "v": sp}
    return c, s


def init_caches(cfg: ModelConfig, env: MeshEnv, batch: int, cache_len: int,
                cross_len: int = 0, kv_quant: bool = False):
    """Full-stack zero caches + spec tree (same treedef as apply_stack ys)."""
    types = cfg.layer_types()
    period = len(cfg.pattern)
    rem = cfg.num_layers % period
    n_sb = cfg.num_layers // period
    tail_c, tail_s = [], []
    for i in range(rem):
        c, s = init_layer_cache(cfg, env, types[i], batch, cache_len,
                                cross_len, kv_quant)
        tail_c.append(c)
        tail_s.append(s)
    scan_c, scan_s = [], []
    for lt in cfg.pattern:
        c, s = init_layer_cache(cfg, env, lt, batch, cache_len, cross_len,
                                kv_quant)
        scan_c.append(jax.tree.map(
            lambda x: jnp.broadcast_to(x, (n_sb,) + x.shape), c))
        scan_s.append(jax.tree.map(lambda sp: P(None, *sp), s,
                                   is_leaf=lambda x: isinstance(x, P)))
    return ({"tail": tuple(tail_c), "scan": tuple(scan_c)},
            {"tail": tuple(tail_s), "scan": tuple(scan_s)})


# ===========================================================================
# Top-level model functions
# ===========================================================================
def _embed_tokens(cfg: ModelConfig, params: Params, env: MeshEnv, tokens):
    h = embed_lookup(env, params["embed"], tokens)
    return h * jnp.asarray(math.sqrt(cfg.d_model), h.dtype)


def _assemble_inputs(cfg: ModelConfig, params: Params, env: MeshEnv, batch):
    """Returns (h, positions, text_offset) handling VLM patch prefix."""
    h = _embed_tokens(cfg, params, env, batch["tokens"])
    offset = 0
    if cfg.frontend == "vit" and "patch_embeds" in batch:
        pe = batch["patch_embeds"].astype(h.dtype)
        h = jnp.concatenate([pe, h], axis=1)
        offset = pe.shape[1]
    S = h.shape[1]
    positions = jnp.arange(S)[None, :].repeat(h.shape[0], 0)
    return h, positions, offset


def _encode(cfg: ModelConfig, params: Params, env: MeshEnv, src_embeds,
            remat: bool = False, unroll: bool = False):
    ecfg = encoder_cfg(cfg)
    h = src_embeds.astype(jnp.dtype(cfg.dtype))
    pos = jnp.arange(h.shape[1])[None, :].repeat(h.shape[0], 0)
    h, _, _ = apply_stack(ecfg, params["encoder"], env, h, mode="encode",
                          positions=pos, remat=remat, unroll=unroll)
    return rms_norm(h, params["enc_norm"], cfg.norm_eps)


def loss_fn(cfg: ModelConfig, params: Params, env: MeshEnv, batch, *,
            remat: bool = True, capacity_factor: float = 1.25,
            triangular: bool = False, unroll: bool = False):
    """batch: tokens (B,S), labels (B,S) [+ patch_embeds | src_embeds].
    Returns (mean loss, metrics dict)."""
    kv_memory = None
    if cfg.enc_dec:
        kv_memory = _encode(cfg, params, env, batch["src_embeds"],
                            remat=remat, unroll=unroll)
    h, positions, offset = _assemble_inputs(cfg, params, env, batch)
    h, _, aux = apply_stack(cfg, params["stack"], env, h, mode="train",
                            positions=positions, kv_memory=kv_memory,
                            remat=remat, capacity_factor=capacity_factor,
                            triangular=triangular, unroll=unroll)
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    if offset:
        h = h[:, offset:]
    table = params["embed"] if cfg.tie_embeddings else params["unembed"]
    tok_loss = fused_unembed_xent(env, h, table, batch["labels"],
                                  transpose_table=cfg.tie_embeddings,
                                  valid_vocab=cfg.vocab_size)
    loss = jnp.mean(tok_loss)
    total = loss + MOE_AUX_WEIGHT * aux
    return total, {"loss": loss, "aux": aux}


def prefill(cfg: ModelConfig, params: Params, env: MeshEnv, batch, *,
            cache_len: int, capacity_factor: float = 1.25,
            unroll: bool = False, triangular: bool = False,
            kv_quant: bool = False, all_positions: bool = False):
    """Returns (last-position logits (B, Vp) vocab-sharded, caches).

    ``all_positions=True`` returns every position's logits (B, S, Vp)
    instead: a cache-free teacher-forced pass that checks decode."""
    kv_memory = None
    cross_len = 0
    if cfg.enc_dec:
        kv_memory = _encode(cfg, params, env, batch["src_embeds"],
                            unroll=unroll)
        cross_len = kv_memory.shape[1]
    h, positions, offset = _assemble_inputs(cfg, params, env, batch)
    caches, _ = init_caches(cfg, env, h.shape[0], cache_len, cross_len,
                            kv_quant=kv_quant)
    h, new_caches, _ = apply_stack(
        cfg, params["stack"], env, h, mode="prefill", positions=positions,
        caches=caches, cache_len=cache_len, kv_memory=kv_memory,
        capacity_factor=capacity_factor, unroll=unroll,
        triangular=triangular)
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    table = params["embed"] if cfg.tie_embeddings else params["unembed"]
    logits = unembed_logits(env, h if all_positions else h[:, -1:], table,
                            transpose_table=cfg.tie_embeddings,
                            valid_vocab=cfg.vocab_size)
    return (logits if all_positions else logits[:, 0]), new_caches


def decode_step(cfg: ModelConfig, params: Params, env: MeshEnv, token,
                pos, caches, *, capacity_factor: float = 2.0,
                unroll: bool = False):
    """token: (B, 1) int32; pos: scalar int32 (position of this token).
    Returns (logits (B, Vp) vocab-sharded, next_token (B,), new caches)."""
    h = _embed_tokens(cfg, params, env, token)
    h, new_caches, _ = apply_stack(
        cfg, params["stack"], env, h, mode="decode", positions=pos,
        caches=caches, capacity_factor=capacity_factor, unroll=unroll)
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    table = params["embed"] if cfg.tie_embeddings else params["unembed"]
    logits = unembed_logits(env, h, table,
                            transpose_table=cfg.tie_embeddings,
                            valid_vocab=cfg.vocab_size)[:, 0]
    next_token = sharded_argmax(env, logits)
    return logits, next_token, new_caches
