"""How far each served token's logit lies below a plain reference's best.

Shared by every serve cell: it takes the reference module's ``forward``
(``bench/reference/<module>.py``), so a new reference writes only its
architecture.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames=("forward", "m_json", "int8"))
def _gaps(params, tokens, targets, valid, forward, m_json, int8):
    m = json.loads(m_json)
    ref = forward(m, params, tokens)
    rms = jnp.sqrt(jnp.mean(ref * ref, axis=-1))
    best = jnp.max(ref, axis=-1)
    if int8:
        targets = jnp.argmax(forward(m, params, tokens, int8=True), -1)
    chosen = jnp.take_along_axis(ref, targets[..., None], -1)[..., 0]
    return jnp.where(valid, (best - chosen) / rms, 0.0)


def logit_gaps(forward, m: dict, params: dict, tokens, targets, valid,
               int8: bool = False):
    """Per position, how far the logit of ``targets`` (the served token)
    lies below the best of ``forward``'s float32 logits, in units of that
    row's logit RMS; with ``int8`` the target is the token the control
    (``forward`` with ``int8=True``) puts first."""
    return _gaps(params, tokens, targets, valid, forward,
                 json.dumps(m, sort_keys=True), int8)
