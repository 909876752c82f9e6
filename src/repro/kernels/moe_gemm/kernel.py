"""Pallas-TPU fused grouped expert SwiGLU (MoE FFN compute).

Input is the capacity-dispatched buffer (E_local, C, d) from the EP
dispatch (repro.models.moe).  One kernel computes, per expert,
    y = (silu(x·Wg) ⊙ (x·Wu)) · Wd
with the ff dimension streamed in blocks: grid = (E, C_blocks, FF_blocks),
FF sequential, the (C_blk, d) output accumulating in VMEM scratch.  The
(C_blk, ff_blk) activation h never touches HBM — that's the fusion win
over three separate grouped GEMMs (h is ~3× the output bytes).

Block shapes are MXU-aligned (128-multiples in C and ff; d rides whole —
d ≤ 2048 for both assigned MoE archs).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _moe_kernel(x_ref, wg_ref, wu_ref, wd_ref, y_ref, acc_scr, *,
                num_ff_blocks: int, ff: int, ff_block: int):
    fi = pl.program_id(2)

    @pl.when(fi == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    x = x_ref[0].astype(jnp.float32)               # (cb, d)
    wg = wg_ref[0].astype(jnp.float32)             # (d, fb)
    wu = wu_ref[0].astype(jnp.float32)
    wd = wd_ref[0].astype(jnp.float32)             # (fb, d)
    # mask the padded tail of the ff dim (OOB block reads are undefined)
    ff_valid = (fi * ff_block + jax.lax.broadcasted_iota(
        jnp.int32, (1, wg.shape[1]), 1)) < ff
    wg = jnp.where(ff_valid, wg, 0.0)
    wu = jnp.where(ff_valid, wu, 0.0)
    wd = jnp.where(ff_valid.reshape(-1, 1), wd, 0.0)
    g = jax.lax.dot_general(x, wg, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    u = jax.lax.dot_general(x, wu, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    h = jax.nn.silu(g) * u                         # (cb, fb) — VMEM only
    acc_scr[...] += jax.lax.dot_general(h, wd, (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    @pl.when(fi == num_ff_blocks - 1)
    def _finalize():
        y_ref[0] = acc_scr[...].astype(y_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("c_block", "ff_block", "interpret"))
def moe_swiglu_tpu(x, wg, wu, wd, *, c_block: int = 128,
                   ff_block: int = 256, interpret: bool = False):
    """x: (E, C, d); wg/wu: (E, d, ff); wd: (E, ff, d) -> (E, C, d)."""
    E, C, d = x.shape
    ff = wg.shape[-1]
    cb = min(c_block, max(C, 8))
    fb = min(ff_block, ff)
    ncb = pl.cdiv(C, cb)
    nfb = pl.cdiv(ff, fb)
    kernel = functools.partial(_moe_kernel, num_ff_blocks=nfb, ff=ff, ff_block=fb)
    return pl.pallas_call(
        kernel,
        grid=(E, ncb, nfb),
        in_specs=[
            pl.BlockSpec((1, cb, d), lambda e, ci, fi: (e, ci, 0)),
            pl.BlockSpec((1, d, fb), lambda e, ci, fi: (e, 0, fi)),
            pl.BlockSpec((1, d, fb), lambda e, ci, fi: (e, 0, fi)),
            pl.BlockSpec((1, fb, d), lambda e, ci, fi: (e, fi, 0)),
        ],
        out_specs=pl.BlockSpec((1, cb, d), lambda e, ci, fi: (e, ci, 0)),
        out_shape=jax.ShapeDtypeStruct((E, C, d), x.dtype),
        scratch_shapes=[pltpu.VMEM((cb, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="mcsa_moe_swiglu",
    )(x, wg, wu, wd)
