"""Pallas TPU kernels (validated in interpret mode on CPU):

  flash_attention — GQA/causal/sliding-window online-softmax attention
  rglru           — chunked RG-LRU linear recurrence (Griffin)
  wkv6            — chunked RWKV-6 state recurrence
  moe_gemm        — fused grouped expert SwiGLU (EP MoE FFN)
  ligd_step       — batched Li-GD projected-GD inner loop (paper hot-spot)
  rmsnorm         — fused RMSNorm

Each subpackage: kernel.py (pl.pallas_call + BlockSpec), ops.py (jit'd
dispatch wrapper), ref.py (pure-jnp oracle).
"""
from . import flash_attention, ligd_step, moe_gemm, rglru, rmsnorm, wkv6
