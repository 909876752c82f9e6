"""Plain reference of the dense decoder the engine serves, in float32, and its int8 control.

A serve configuration names its reference module in ``model.reference``
(``bench/reference/<module>.py``); this one is ``lm``.  A reference
module supplies everything of the serve harness that depends on the
architecture, and the harness takes it from nowhere else:

- ``forward(m, params, tokens, int8=False)``: the logits of every
  position (``bench/logit_gaps.py`` compares the served tokens by them);
- ``weight_rule(m)``: for every leaf name of the program's parameter
  tree, the axes of the leaf's shape whose product is its fan-in (the
  harness draws it normal with variance 1/fan-in), or None for a norm
  weight (0.1 x normal, entering as 1 + w); it refuses a ``model`` group
  that states what the module does not cover;
- ``decode_step_work(m, positions)`` and ``prefill_flops(m, prompt_len)``:
  the operations and bytes the per-layer readers count.

This module covers the dense decoder: token embedding scaled by
sqrt(d_model); pre-norm blocks with RMSNorm whose weight enters as
``1 + w``; grouped query attention with rotary embeddings on the two
halves of each head, causal, scaled by head_dim^-1/2; a SwiGLU MLP; a
final RMSNorm and an untied unembedding; bfloat16 weights.  A ``model``
group that states any other key, experts or a layer pattern among them,
is refused.  Its counts are the dense ones of ``bench/flops.py``.

The forward pass runs over whole sequences, with no cache, no batching of
slots and no kernel.  It imports nothing of the program: it reads the
weights the harness made, by name, and upcasts one layer at a time inside
a scan so that it fits beside them.  Matmuls run at
``precision="highest"``.

The control computes every weight matmul in int8 instead (weights
quantized per output channel, activations per token, int32 accumulation),
one precision below the bfloat16 the configuration states; attention and
the norms stay in float32.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.flops import decode_step_work, prefill_flops  # noqa: F401

HIGHEST = jax.lax.Precision.HIGHEST

# the keys of the model group this reference reads; ``kind``, ``model_id``
# and ``reference`` name things and are not read
KEYS = ("num_layers", "d_model", "num_heads", "num_kv_heads", "head_dim",
        "d_ff", "vocab_size", "tie_embeddings", "rope_theta", "norm_eps",
        "dtype")
NAMING = ("kind", "model_id", "reference")

# leaf name -> the axes of its shape whose product is its fan-in, or None
# for a norm weight; shapes as the program stacks them over layers:
# wq/wk/wv (L, d, heads, hd), wo (L, H, hd, d), wg/wu (L, d, ff),
# wd (L, ff, d), embed (V, d), unembed (d, V)
WEIGHTS = {
    "embed": (-1,),
    "wq": (-3,), "wk": (-3,), "wv": (-3,), "wo": (-3, -2),
    "wg": (-2,), "wu": (-2,), "wd": (-2,), "unembed": (-2,),
    "ln1": None, "ln2": None, "final_norm": None,
}


def weight_rule(m: dict) -> dict:
    """The weight-draw rule, for a ``model`` group this reference covers;
    any other is an error that names what it does not cover."""
    other = sorted(set(m) - set(KEYS) - set(NAMING))
    if other:
        raise ValueError(
            f"the dense reference `lm` does not cover {other} (experts, "
            f"layer patterns and other mechanisms need a reference module "
            f"of their own)")
    missing = [k for k in KEYS if k not in m]
    if missing:
        raise ValueError(f"the model group does not state {missing}, which "
                         f"the dense reference `lm` reads")
    if m["tie_embeddings"] or m["dtype"] != "bfloat16":
        raise ValueError("the dense reference `lm` does not cover tied "
                         "embeddings or weights other than bfloat16")
    return WEIGHTS


def _mm(x, w, int8: bool):
    """x (..., k) @ w (k, n) in float32, or in int8 for the control."""
    w = w.astype(jnp.float32)
    if not int8:
        return jnp.matmul(x, w, precision=HIGHEST)
    sx = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0
    sx = jnp.where(sx > 0, sx, 1.0)
    sw = jnp.max(jnp.abs(w), axis=0, keepdims=True) / 127.0
    sw = jnp.where(sw > 0, sw, 1.0)
    xq = jnp.round(x / sx).astype(jnp.int8)
    wq = jnp.round(w / sw).astype(jnp.int8)
    acc = jax.lax.dot_general(xq, wq, (((x.ndim - 1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * sx * sw


def _rms(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w.astype(jnp.float32))


def _rope(x, theta):
    """x (B, S, H, hd): rotate the two halves of each head by position."""
    S, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def forward(m: dict, params: dict, tokens, int8: bool = False):
    """Logits (B, S, vocab) of every position of ``tokens`` (B, S)."""
    d, H, Hkv, hd = m["d_model"], m["num_heads"], m["num_kv_heads"], \
        m["head_dim"]
    eps, theta = m["norm_eps"], m["rope_theta"]
    B, S = tokens.shape
    x = params["embed"][tokens].astype(jnp.float32) * math.sqrt(d)
    causal = jnp.tril(jnp.ones((S, S), bool))

    def block(h, p):
        a = _rms(h, p["ln1"], eps)
        mix = p["mix"]
        q = _mm(a, mix["wq"].reshape(d, H * hd), int8).reshape(B, S, H, hd)
        k = _mm(a, mix["wk"].reshape(d, Hkv * hd), int8).reshape(
            B, S, Hkv, hd)
        v = _mm(a, mix["wv"].reshape(d, Hkv * hd), int8).reshape(
            B, S, Hkv, hd)
        q, k = _rope(q, theta), _rope(k, theta)
        q = q.reshape(B, S, Hkv, H // Hkv, hd)
        s = jnp.einsum("bqgrd,bkgd->bgrqk", q, k, precision=HIGHEST)
        s = jnp.where(causal, s * hd ** -0.5, -jnp.inf)
        o = jnp.einsum("bgrqk,bkgd->bqgrd", jax.nn.softmax(s, -1), v,
                       precision=HIGHEST).reshape(B, S, H * hd)
        h = h + _mm(o, mix["wo"].reshape(H * hd, d), int8)
        b = _rms(h, p["ln2"], eps)
        f = p["ffn"]
        g = _mm(b, f["wg"], int8)
        u = _mm(b, f["wu"], int8)
        return h + _mm(jax.nn.silu(g) * u, f["wd"], int8), None

    stack = params["stack"]
    if stack["tail"] or len(stack["scan"]) != 1:
        raise ValueError("the reference expects one stacked block kind")
    h, _ = jax.lax.scan(block, x, stack["scan"][0])
    h = _rms(h, params["final_norm"], eps)
    return _mm(h, params["unembed"], int8)[..., :m["vocab_size"]]
