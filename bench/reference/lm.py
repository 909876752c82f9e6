"""Plain reference of the served decoder, in float32, and its int8 control.

A straightforward forward pass over whole sequences, written from the
configuration's equations (token embedding scaled by sqrt(d_model);
pre-norm blocks with RMSNorm whose weight enters as ``1 + w``; grouped
query attention with rotary embeddings on the two halves of each head,
causal, scaled by head_dim^-1/2; a SwiGLU MLP; a final RMSNorm and an
untied unembedding), with no cache, no batching of slots and no kernel.
It imports nothing of the program: it reads the weights the harness made,
by name, and upcasts one layer at a time inside a scan so that it fits
beside them.  Matmuls run at ``precision="highest"``.

The control computes every weight matmul in int8 instead (weights
quantized per output channel, activations per token, int32 accumulation),
one precision below the bfloat16 the configuration states; attention and
the norms stay in float32.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


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


@functools.partial(jax.jit, static_argnames=("m_items", "int8"))
def _gaps(params, tokens, targets, valid, m_items, int8):
    m = dict(m_items)
    ref = forward(m, params, tokens)
    rms = jnp.sqrt(jnp.mean(ref * ref, axis=-1))
    best = jnp.max(ref, axis=-1)
    if int8:
        targets = jnp.argmax(forward(m, params, tokens, int8=True), -1)
    chosen = jnp.take_along_axis(ref, targets[..., None], -1)[..., 0]
    return jnp.where(valid, (best - chosen) / rms, 0.0)


def logit_gaps(m: dict, params: dict, tokens, targets, valid,
               int8: bool = False):
    """Per position, how far the logit of ``targets`` (the served token)
    lies below the reference's best, in units of that row's logit RMS;
    with ``int8`` the target is the token the control puts first."""
    return _gaps(params, tokens, targets, valid,
                 tuple(sorted(m.items())), int8)
