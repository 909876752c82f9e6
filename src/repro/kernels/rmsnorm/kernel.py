"""Pallas-TPU fused RMSNorm: one pass over rows, f32 statistics in VMEM.

Grid = (row_blocks,); each step normalizes an (rb, d) tile.  Fusing the
mean-square reduction with the scale keeps the tile resident in VMEM
(2 HBM touches per element instead of 3 for the unfused norm).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)             # (rb, d)
    w = w_ref[...].astype(jnp.float32)             # (1, d)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps) * (1.0 + w)
    o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("eps", "row_block", "interpret"))
def rmsnorm_tpu(x, w, *, eps: float = 1e-6, row_block: int = 256,
                interpret: bool = False):
    """x: (..., d); w: (d,) -> same shape/dtype as x."""
    orig_shape = x.shape
    d = orig_shape[-1]
    xf = x.reshape(-1, d)
    R = xf.shape[0]
    rb = min(row_block, max(R, 8))
    nb = pl.cdiv(R, rb)
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=(nb,),
        in_specs=[pl.BlockSpec((rb, d), lambda i: (i, 0)),
                  pl.BlockSpec((1, d), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((rb, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="mcsa_rmsnorm",
    )(xf, w.reshape(1, d))
    return out.reshape(orig_shape)
