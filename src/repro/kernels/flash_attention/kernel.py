"""Pallas-TPU flash attention with GQA, causal masking, and sliding window.

TPU-native design (vs. the CUDA flash-attention algorithm):
  * Grid = (batch·q_heads, q_blocks, kv_blocks); the kv dim is sequential
    ("arbitrary") so the online-softmax state lives in VMEM scratch across
    kv iterations — the TPU analogue of a CUDA thread-block's shared-memory
    accumulator.
  * Block shapes are MXU-aligned: q/kv blocks are multiples of 128 in the
    seq dim (8×128 VPU lanes; 128×128 MXU tiles), head_dim rides whole.
  * Causal + sliding-window block skipping happens at the GRID level via
    ``pl.when`` on block indices — skipped blocks issue no MXU work.
  * GQA maps q-head h to kv-head h // (Hq//Hkv) in the BlockSpec index
    maps — no materialized repeat_kv.

VMEM working set per step (defaults qb=kb=512, hd=128, f32):
  q 256 KiB + k/v 512 KiB + acc 256 KiB + scores 1 MiB ≈ 2 MiB  « 16 MiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                 scale: float, causal: bool, window: int, q_block: int,
                 kv_block: int, seq_q: int, seq_kv: int, num_kv_blocks: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * q_block
    k_start = ki * kv_block
    # Block-level skip: block fully in the causal future, or fully outside
    # the sliding window.
    needed = jnp.asarray(True)
    if causal:
        needed = jnp.logical_and(needed, k_start <= q_start + q_block - 1)
    if window > 0:
        # newest q position in block attends back `window`; block dead if
        # its newest k is older than (oldest q - window).
        needed = jnp.logical_and(
            needed, (k_start + kv_block - 1) > (q_start - window))

    @pl.when(needed)
    def _compute():
        q = q_ref[0].astype(jnp.float32)              # (qb, hd)
        k = k_ref[0].astype(jnp.float32)              # (kb, hd)
        v = v_ref[0].astype(jnp.float32)
        # zero padded kv rows: the final seq block may read OOB (padded)
        # values, and 0-weight × garbage would still poison the p @ v MAC.
        kv_valid = (k_start + jax.lax.broadcasted_iota(
            jnp.int32, (v.shape[0], 1), 0)) < seq_kv
        v = jnp.where(kv_valid, v, 0.0)
        k = jnp.where(kv_valid, k, 0.0)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = jnp.logical_and(q_pos < seq_q, k_pos < seq_kv)
        if causal:
            mask = jnp.logical_and(mask, q_pos >= k_pos)
        if window > 0:
            mask = jnp.logical_and(mask, (q_pos - k_pos) < window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1)
        acc_scr[...] = (acc_scr[...] * corr[:, None]
                        + jax.lax.dot_general(
                            p, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_scr[...] = m_new

    @pl.when(ki == num_kv_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "q_block", "kv_block", "interpret"))
def flash_attention_tpu(q, k, v, *, causal: bool = True, window: int = 0,
                        q_block: int = 512, kv_block: int = 512,
                        interpret: bool = False):
    """q: (B, Hq, Sq, hd); k/v: (B, Hkv, Skv, hd) -> (B, Hq, Sq, hd)."""
    B, Hq, Sq, hd = q.shape
    _, Hkv, Skv, _ = k.shape
    rep = Hq // Hkv
    scale = hd ** -0.5
    qb = min(q_block, max(Sq, 8))
    kb = min(kv_block, max(Skv, 8))
    nq = pl.cdiv(Sq, qb)
    nk = pl.cdiv(Skv, kb)

    qf = q.reshape(B * Hq, Sq, hd)
    kf = k.reshape(B * Hkv, Skv, hd)
    vf = v.reshape(B * Hkv, Skv, hd)

    def q_index(bh, qi, ki):
        return (bh, qi, 0)

    def kv_index(bh, qi, ki):
        return ((bh // Hq) * Hkv + (bh % Hq) // rep, ki, 0)

    kernel = functools.partial(
        _attn_kernel, scale=scale, causal=causal, window=window,
        q_block=qb, kv_block=kb, seq_q=Sq, seq_kv=Skv, num_kv_blocks=nk)

    out = pl.pallas_call(
        kernel,
        grid=(B * Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, qb, hd), q_index),
            pl.BlockSpec((1, kb, hd), kv_index),
            pl.BlockSpec((1, kb, hd), kv_index),
        ],
        out_specs=pl.BlockSpec((1, qb, hd), q_index),
        out_shape=jax.ShapeDtypeStruct((B * Hq, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((qb,), jnp.float32),
            pltpu.VMEM((qb,), jnp.float32),
            pltpu.VMEM((qb, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="mcsa_flash_attention",
    )(qf, kf, vf)
    return out.reshape(B, Hq, Sq, hd)
