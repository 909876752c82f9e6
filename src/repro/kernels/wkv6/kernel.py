"""Pallas-TPU chunked WKV6 recurrence (RWKV-6 "Finch" time mix).

Per head (state S ∈ R^{n×n}, n = head dim, k-major):
    y_t = Sᵀ r_t + v_t ((u ⊙ k_t)·r_t)
    S  ← diag(w_t) S + k_t v_tᵀ

Grid = (batch·heads, time_chunks); time sequential with S in VMEM scratch
(n=64 → 16 KiB f32).  Within a chunk the update runs as an in-VMEM fori
loop over timesteps — outer-product MACs on the VPU/MXU with zero HBM
traffic for the state.  This is the TPU analogue of the CUDA wkv kernel's
shared-memory state (the GPU version keeps S in registers per thread;
VMEM scratch is the TPU equivalent).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv6_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, y_ref, s_scr, *,
                 chunk: int, seq: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    r = r_ref[0].astype(jnp.float32)          # (chunk, n)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    w = w_ref[0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)          # (1, n) -> broadcast
    t0 = ci * chunk
    tpos = t0 + jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
    valid = tpos < seq
    # identity elements for padded steps: w=1 (no decay), k=v=r=0
    w = jnp.where(valid, w, 1.0)
    r = jnp.where(valid, r, 0.0)
    k = jnp.where(valid, k, 0.0)
    v = jnp.where(valid, v, 0.0)

    def step(t, carry):
        s, y = carry
        rt = jax.lax.dynamic_slice_in_dim(r, t, 1, 0)      # (1, n)
        kt = jax.lax.dynamic_slice_in_dim(k, t, 1, 0)
        vt = jax.lax.dynamic_slice_in_dim(v, t, 1, 0)
        wt = jax.lax.dynamic_slice_in_dim(w, t, 1, 0)
        yt = (rt @ s) + vt * jnp.sum(rt * (u * kt), axis=1, keepdims=True)
        y = jax.lax.dynamic_update_slice_in_dim(y, yt, t, 0)
        s = wt.T * s + kt.T @ vt                           # (n, n)
        return s, y

    y0 = jnp.zeros_like(r)
    s, y = jax.lax.fori_loop(0, chunk, step, (s_scr[...], y0))
    s_scr[...] = s
    y_ref[0] = y.astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6_tpu(r, k, v, w, u, *, chunk: int = 128, interpret: bool = False):
    """r,k,v,w: (B, H, S, n); u: (H, n) -> y: (B, H, S, n) f32.

    State layout s[k_dim, v_dim]; y_t = s_{t-1}ᵀ r_t + bonus (matches
    repro.models.rwkv.wkv6_scan)."""
    B, H, S, n = r.shape
    ck = min(chunk, max(S, 8))
    nc = pl.cdiv(S, ck)
    rf = r.reshape(B * H, S, n)
    kf = k.reshape(B * H, S, n)
    vf = v.reshape(B * H, S, n)
    wf = w.reshape(B * H, S, n)
    uf = jnp.broadcast_to(u[None], (B, H, n)).reshape(B * H, 1, n)
    kernel = functools.partial(_wkv6_kernel, chunk=ck, seq=S)
    y = pl.pallas_call(
        kernel,
        grid=(B * H, nc),
        in_specs=[
            pl.BlockSpec((1, ck, n), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, ck, n), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, ck, n), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, ck, n), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, 1, n), lambda bh, ci: (bh, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, ck, n), lambda bh, ci: (bh, ci, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((n, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="mcsa_wkv6",
    )(rf, kf, vf, wf, uf)
    return y.reshape(B, H, S, n)
