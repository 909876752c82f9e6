"""The chip's peaks, and the operations and bytes of the dense decoder's work.

``peaks`` and ``least_time_s`` serve every cell.  The rest counts the
dense decoder from shapes alone: its parameter arithmetic follows the
program's own layer equations (GQA attention with separate q/k/v/o
projections, a three-matrix SwiGLU MLP, RMSNorm weights, untied
embedding and unembedding) and is kept here, apart from the program, so
that the yardstick does not move with it.  The dense reference
(``bench/reference/lm.py``) hands ``decode_step_work`` and
``prefill_flops`` on to the readers; a reference module of another
architecture supplies its own.  Every function takes the configuration's
``model`` group as a dict.
"""
from __future__ import annotations

import json
import os
from typing import Dict, Sequence

PEAKS_FILE = os.path.join(os.path.dirname(__file__), "peaks.json")


def peaks(device_kind: str) -> Dict[str, float]:
    """The chip's published peaks; an unknown kind is an error."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def layer_matmul_params(m: dict) -> int:
    d, hd = m["d_model"], m["head_dim"]
    attn = d * m["num_heads"] * hd * 2 + d * m["num_kv_heads"] * hd * 2
    return attn + 3 * d * m["d_ff"]


def num_params(m: dict) -> int:
    """Every parameter the program holds for this model."""
    d, V = m["d_model"], m["vocab_size"]
    per_layer = layer_matmul_params(m) + 2 * d            # two norms
    tables = V * d * (1 if m["tie_embeddings"] else 2)
    return m["num_layers"] * per_layer + d + tables


def matmul_params(m: dict) -> int:
    """Parameters that do matmul work for one token: the body and the
    unembedding (the embedding is a lookup)."""
    return m["num_layers"] * layer_matmul_params(m) + m["vocab_size"] * \
        m["d_model"]


def weight_bytes(m: dict, elem_bytes: int = 2) -> int:
    """Bytes of every weight a decode step must read: the matmul weights
    and the norms (the embedding rows it gathers are negligible)."""
    return elem_bytes * (matmul_params(m) + (2 * m["num_layers"] + 1)
                         * m["d_model"])


def kv_bytes_per_token(m: dict, elem_bytes: int = 2) -> int:
    """K and V of one position over all layers."""
    return m["num_layers"] * 2 * m["num_kv_heads"] * m["head_dim"] * \
        elem_bytes


def attention_flops(m: dict, context: float) -> float:
    """Scores and weighted sum of one query against ``context`` keys,
    over all layers."""
    return 4.0 * m["num_layers"] * m["num_heads"] * m["head_dim"] * context


def decode_step_work(m: dict, positions: Sequence[int]) -> Dict[str, float]:
    """Operations and HBM bytes one decode step needs for the active slots
    at ``positions`` (the position each slot writes).  Each slot attends
    over ``position + 1`` keys and reads that much of the cache."""
    n = len(positions)
    ctx = float(sum(p + 1 for p in positions))
    flops = 2.0 * matmul_params(m) * n + attention_flops(m, ctx)
    nbytes = weight_bytes(m) + kv_bytes_per_token(m) * ctx
    return {"flops": flops, "bytes": nbytes}


def prefill_flops(m: dict, prompt_len: int) -> float:
    """One prompt's prefill: the body over every position, causal
    attention, and the unembedding at the last position only."""
    S = prompt_len
    body = matmul_params(m) - m["vocab_size"] * m["d_model"]
    return (2.0 * body * S + 2.0 * m["vocab_size"] * m["d_model"]
            + attention_flops(m, S * (S + 1) / 2.0))


def least_time_s(work: Dict[str, float], peak: Dict[str, float]):
    """(seconds, bound): the larger of operations over peak FLOP/s and
    bytes over HBM bandwidth, and which of the two it was."""
    t_c = work["flops"] / peak["bf16_flops_per_s"]
    t_m = work["bytes"] / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
