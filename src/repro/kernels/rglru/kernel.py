"""Pallas-TPU chunked RG-LRU linear recurrence.

h_t = a_t ⊙ h_{t-1} + b_t over time, with the time axis chunked: grid =
(batch, channel_blocks, time_chunks); the time dim is sequential
("arbitrary") with the running state h in VMEM scratch.  Within a chunk the
recurrence runs as an unrolled log-depth (Blelloch-style) scan over the
chunk's rows — pure VPU work on an (chunk, channel_block) tile.

This is the TPU adaptation of Griffin's scan: HBM traffic is exactly one
read of (a, b) + one write of h per element (memory-bound roofline), with
the sequential dependency confined to VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(a_ref, b_ref, o_ref, h_scr, *, chunk: int, seq: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    a = a_ref[0].astype(jnp.float32)              # (chunk, cb)
    b = b_ref[0].astype(jnp.float32)
    # mask padded time rows to the identity element (a=1, b=0)
    t_pos = ci * chunk + jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
    valid = t_pos < seq
    a = jnp.where(valid, a, 1.0)
    b = jnp.where(valid, b, 0.0)

    # Inclusive scan over rows via log-depth prefix combine:
    #   (A, B)_t ∘ (A, B)_{t-k}  :=  (A_t·A_{t-k},  A_t·B_{t-k} + B_t)
    A, Bv = a, b
    shift = 1
    while shift < chunk:
        A_prev = jnp.pad(A, ((shift, 0), (0, 0)),
                         constant_values=1.0)[:chunk]
        B_prev = jnp.pad(Bv, ((shift, 0), (0, 0)))[:chunk]
        Bv = A * B_prev + Bv
        A = A * A_prev
        shift *= 2
    # fold in carry state: h_t = A_t · h_in + B_t
    h = A * h_scr[...][None, :] + Bv
    h_scr[...] = h[chunk - 1]
    o_ref[0] = h.astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("chunk", "channel_block", "interpret"))
def rglru_scan_tpu(a, b, *, chunk: int = 256, channel_block: int = 512,
                   interpret: bool = False):
    """a, b: (B, S, C) -> h: (B, S, C) with h_t = a_t h_{t-1} + b_t."""
    B, S, C = a.shape
    ck = min(chunk, max(S, 8))
    cb = min(channel_block, C)
    nc = pl.cdiv(S, ck)
    ncb = pl.cdiv(C, cb)
    kernel = functools.partial(_rglru_kernel, chunk=ck, seq=S)
    return pl.pallas_call(
        kernel,
        grid=(B, ncb, nc),
        in_specs=[
            pl.BlockSpec((1, ck, cb), lambda bi, cbi, ci: (bi, ci, cbi)),
            pl.BlockSpec((1, ck, cb), lambda bi, cbi, ci: (bi, ci, cbi)),
        ],
        out_specs=pl.BlockSpec((1, ck, cb), lambda bi, cbi, ci: (bi, ci, cbi)),
        out_shape=jax.ShapeDtypeStruct((B, S, C), a.dtype),
        scratch_shapes=[pltpu.VMEM((cb,), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="mcsa_rglru_scan",
    )(a, b)
