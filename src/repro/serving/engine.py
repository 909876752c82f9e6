"""Batched serving engine: continuous-batching prefill/decode over slot state.

The engine owns a fixed pool of batch slots (the compiled decode program has
a static batch dim).  Requests are admitted into free slots; each engine
step runs ONE fused decode for all active slots; finished sequences free
their slots.  Prefill runs per-request (padded to bucket lengths to bound
compilation count).

This is the edge-server role of the MCSA system: the planner (Li-GD)
decides per-user split points and the resource share r_i; the engine is
what actually burns those compute units.  ``InferenceEngine`` also serves
unsplit models — the Edge-Only baseline — and is exercised CPU-scale in
examples/serve_split.py.  The closed-loop data plane
(:mod:`repro.serving.dataplane`) runs one engine per edge server with
slot counts derived from admission r-budgets; see docs/ARCHITECTURE.md
("Serving data plane").

Every step is a named span (:mod:`repro.telemetry.spans`):
``mcsa.engine.step`` holds ``engine.admit`` (per admission
``engine.prefill``, ``engine.first_token`` and ``engine.slot_write``, all
carrying the request's ``rid``), ``engine.decode`` (the dispatch),
``engine.token_pull`` (the host waiting for the step's tokens) and
``engine.emit``; docs/ARCHITECTURE.md ("Spans").
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import transformer as tfm
from repro.runtime.meshenv import CPU_ENV, MeshEnv
from repro.telemetry.spans import span

Params = Dict[str, Any]


@dataclasses.dataclass
class Request:
    rid: int
    tokens: np.ndarray              # prompt (S,)
    max_new: int
    out: List[int] = dataclasses.field(default_factory=list)
    t_submit: float = 0.0           # host clock at submit (perf_counter)

    @property
    def done(self) -> bool:
        return len(self.out) >= self.max_new


@dataclasses.dataclass
class DecodeState:
    caches: Any                     # stacked KV/recurrent caches (B slots)
    last_token: jnp.ndarray         # (B, 1)
    pos: np.ndarray                 # (B,) per-slot positions
    active: np.ndarray              # (B,) bool


class CacheOverflowError(RuntimeError):
    """A migrated cache prefix does not fit the target slot's cache.

    Raised by :meth:`InferenceEngine.import_cache` when the imported
    prefix would leave no room for the remaining decode writes
    (``pos + max_new > cache_len`` — "exactly fills" counts: position
    ``pos`` itself must still be writable), and by the per-slot cache
    write when an incoming leaf exceeds the pool leaf along any axis.
    Silently cropping either case would corrupt the stream's KV state,
    so both fail loudly instead (see tests/test_engine.py)."""


class IncompleteRunError(RuntimeError):
    """``run_to_completion`` ran out of steps with work still in flight.

    Carries the surviving request ids so callers can recover or account
    for them instead of silently losing requests.  ``partial`` holds the
    outputs produced so far for every request the engine has seen."""

    def __init__(self, queued: List[int], active: List[int],
                 partial: Dict[int, List[int]]):
        super().__init__(
            f"run_to_completion exhausted max_steps with "
            f"{len(queued)} queued and {len(active)} active request(s)")
        self.queued = queued
        self.active = active
        self.partial = partial


def _bucket(n: int, buckets=(64, 128, 256, 512, 1024, 2048, 4096)) -> int:
    for b in buckets:
        if n <= b:
            return b
    return -(-n // 4096) * 4096


class InferenceEngine:
    def __init__(self, cfg: ModelConfig, params: Params, *,
                 env: MeshEnv = CPU_ENV, slots: int = 4,
                 cache_len: int = 512):
        self.cfg = cfg
        self.params = params
        self.env = env
        self.slots = slots
        self.cache_len = cache_len
        self.requests: Dict[int, Request] = {}
        self.slot_of: Dict[int, int] = {}
        caches, _ = tfm.init_caches(cfg, env, slots, cache_len)
        # single-slot template with the SAME cache_len: leaf shapes match
        # the pool everywhere except the slot axis, which is how
        # export/import find the slot and cache-length axes per leaf
        # (recurrent-state leaves have no cache-length axis and ship whole)
        self._tmpl, _ = tfm.init_caches(cfg, env, 1, cache_len)
        self.state = DecodeState(
            caches=caches,
            last_token=jnp.zeros((slots, 1), jnp.int32),
            pos=np.zeros((slots,), np.int64),
            active=np.zeros((slots,), bool))
        self._queue: List[Request] = []
        self._next_rid = 0

        @functools.partial(jax.jit, static_argnames=("prompt_len",))
        def _prefill(params, tokens, prompt_len):
            logits, caches = tfm.prefill(cfg, params, env,
                                         {"tokens": tokens},
                                         cache_len=cache_len)
            return logits, caches

        @jax.jit
        def _decode(params, token, pos_vec, caches):
            # pos_vec: (slots,) per-slot positions — decode_step supports
            # vector positions (per-row cache scatter + per-row masks).
            return tfm.decode_step(cfg, params, env, token,
                                   pos_vec, caches)

        self._prefill_fn = _prefill
        self._decode_fn = _decode

    # ------------------------------------------------------------------
    @property
    def free_slots(self) -> int:
        """Number of slots not currently running a request."""
        return int(self.slots - self.state.active.sum())

    def submit(self, tokens: np.ndarray, max_new: int) -> int:
        rid = self._next_rid
        self._next_rid += 1
        self._queue.append(Request(rid=rid, tokens=np.asarray(tokens),
                                   max_new=max_new,
                                   t_submit=time.perf_counter()))
        return rid

    def admit(self) -> List[int]:
        """Admit queued requests into free slots, FIFO.  Each admission
        prefills the prompt and emits the first token.  Returns the rids
        admitted this call, in admission order."""
        with span("engine.admit"):
            admitted: List[int] = []
            free = [i for i in range(self.slots) if not self.state.active[i]]
            while free and self._queue:
                slot = free.pop(0)
                req = self._queue.pop(0)
                S = len(req.tokens)
                Sp = _bucket(S)
                prompt = np.zeros((1, Sp), np.int32)
                prompt[0, :S] = req.tokens
                # NOTE: right-pad + prefill at padded length is wasteful
                # but simple; positions beyond S are causally masked out for
                # the last-token logits because we re-decode from position
                # S below.
                waited = time.perf_counter() - req.t_submit
                with span("engine.prefill", rid=req.rid, prompt_len=S,
                          queued_ms=waited * 1e3):
                    logits, caches = self._prefill_fn(
                        self.params, jnp.asarray(prompt[:, :S]), prompt_len=S)
                with span("engine.first_token", rid=req.rid):
                    nxt = int(jnp.argmax(logits[0, :self.cfg.vocab_size]))
                req.out.append(nxt)
                # copy this request's caches into its slot (scan-stacked
                # cache leaves carry a leading superblock axis — the slot
                # axis is wherever the pool is `slots`-wide and the request
                # is 1-wide)
                with span("engine.slot_write", rid=req.rid):
                    self.state.caches = jax.tree.map(
                        lambda pool, one: _slot_write(pool, one, slot,
                                                      self.slots),
                        self.state.caches, caches)
                    lt = self.state.last_token.at[slot, 0].set(nxt)
                    self.state.last_token = lt
                self.state.pos[slot] = S
                self.state.active[slot] = True
                self.requests[req.rid] = req
                if req.done:
                    # max_new == 1: the prefill token satisfied the request
                    # (the data plane hits this re-prefilling a migrated
                    # stream with one token left) — free the slot at once.
                    self.state.active[slot] = False
                    free.insert(0, slot)
                else:
                    self.slot_of[req.rid] = slot
                admitted.append(req.rid)
            return admitted

    # Kept for callers/tests predating the public ``admit``.
    _admit = admit

    def cancel(self, rid: int) -> List[int]:
        """Abort a request (queued or active), freeing its slot.

        Returns the tokens produced so far (empty if it never left the
        queue).  The request is forgotten entirely — used by the data
        plane for deadline timeouts and mid-stream migration, where the
        produced prefix is re-prefilled elsewhere."""
        with span("engine.cancel", rid=rid):
            for i, req in enumerate(self._queue):
                if req.rid == rid:
                    self._queue.pop(i)
                    return list(req.out)
            slot = self.slot_of.pop(rid, None)
            if slot is not None:
                self.state.active[slot] = False
            req = self.requests.pop(rid, None)
            if req is None:
                raise KeyError(f"unknown rid {rid}")
            return list(req.out)

    def pop_result(self, rid: int) -> List[int]:
        """Remove a finished (or cancelled-from-queue) request and return
        its output tokens, releasing the engine's reference to it."""
        req = self.requests.pop(rid)
        self.slot_of.pop(rid, None)
        return list(req.out)

    # -- KV-cache migration --------------------------------------------
    def export_cache(self, rid: int):
        """Extract an active stream's cache leaves for migration.

        Returns ``(leaves, pos)``: the request's per-slot cache pytree,
        each leaf sliced to its slot and cropped to the ``pos`` filled
        positions along the cache-length axis (leaves without one — e.g.
        recurrent state, local-attention windows — ship whole).  The
        engine state is untouched; pair with :meth:`cancel` to actually
        evict the stream.  A peer engine resumes it bit-for-bit via
        :meth:`import_cache` — the data plane uses this to *migrate* a
        KV cache instead of re-prefilling (docs/ARCHITECTURE.md,
        "Serving data plane")."""
        slot = self.slot_of.get(rid)
        if slot is None:
            raise KeyError(f"rid {rid} has no active slot")
        pos = int(self.state.pos[slot])

        def take(pool, tmpl):
            s_ax, c_ax = _cache_axes(pool.shape, tmpl.shape, self.slots,
                                     self.cache_len)
            idx = [slice(None)] * pool.ndim
            idx[s_ax] = slice(slot, slot + 1)
            if c_ax is not None:
                idx[c_ax] = slice(0, pos)
            return pool[tuple(idx)]

        leaves = jax.tree.map(take, self.state.caches, self._tmpl)
        return leaves, pos

    def import_cache(self, tokens: np.ndarray, max_new: int, leaves,
                     pos: int) -> int:
        """Resume a migrated stream from its shipped cache prefix.

        ``tokens`` is the full context so far (prompt + produced — its
        last entry becomes the decode input), ``max_new`` the tokens
        still to generate, ``(leaves, pos)`` what the source engine's
        :meth:`export_cache` returned.  Each leaf is zero-padded from
        ``pos`` back to this pool's ``cache_len`` and written into a
        free slot; decode then continues exactly where the source
        stopped (no prefill recompute — that is the point).

        Raises :class:`CacheOverflowError` when the prefix plus the
        remaining decode writes do not fit (``pos + max_new >
        cache_len``; a prefix that *exactly fills* the cache already
        overflows, because position ``pos`` must still be written), and
        ``RuntimeError`` when no slot is free — callers gate on
        :attr:`free_slots` like they do for :meth:`admit`."""
        with span("engine.import_cache"):
            pos = int(pos)
            tokens = np.asarray(tokens)
            if max_new < 1:
                raise ValueError("import_cache needs max_new >= 1 (a "
                                 "finished stream has nothing to migrate)")
            if pos < 1 or len(tokens) < 1:
                raise ValueError("import_cache needs a non-empty prefix")
            if pos + max_new > self.cache_len:
                raise CacheOverflowError(
                    f"migrated prefix (pos={pos}) + {max_new} decode "
                    f"position(s) exceed cache_len={self.cache_len}")
            free = [i for i in range(self.slots) if not self.state.active[i]]
            if not free:
                raise RuntimeError("import_cache: no free slot")
            slot = free[0]

            def put(pool, tmpl, one):
                s_ax, c_ax = _cache_axes(pool.shape, tmpl.shape, self.slots,
                                         self.cache_len)
                if c_ax is not None and one.shape[c_ax] < pool.shape[c_ax]:
                    pads = [(0, 0)] * pool.ndim
                    pads[c_ax] = (0, pool.shape[c_ax] - one.shape[c_ax])
                    one = jnp.pad(one, pads)
                return _slot_write(pool, one, slot, self.slots)

            self.state.caches = jax.tree.map(put, self.state.caches,
                                             self._tmpl, leaves)
            rid = self._next_rid
            self._next_rid += 1
            req = Request(rid=rid, tokens=tokens, max_new=max_new)
            self.state.last_token = self.state.last_token.at[slot, 0].set(
                int(tokens[-1]))
            self.state.pos[slot] = pos
            self.state.active[slot] = True
            self.requests[rid] = req
            self.slot_of[rid] = slot
            return rid

    # ------------------------------------------------------------------
    def step(self) -> List[Tuple[int, int]]:
        """Admit + one decode for all active slots.
        Returns [(rid, token)] emitted this step."""
        with span("engine.step", active=len(self.slot_of),
                  queued=len(self._queue)):
            self.admit()
            if not self.state.active.any():
                return []
            with span("engine.decode"):
                logits, nxt, caches = self._decode_fn(
                    self.params, self.state.last_token,
                    jnp.asarray(self.state.pos, jnp.int32), self.state.caches)
                self.state.caches = caches
                self.state.last_token = nxt[:, None]
            with span("engine.token_pull"):
                nxt_np = np.asarray(nxt)
            with span("engine.emit"):
                emitted = []
                for rid, slot in list(self.slot_of.items()):
                    if not self.state.active[slot]:
                        continue
                    req = self.requests[rid]
                    tok = int(nxt_np[slot])
                    req.out.append(tok)
                    self.state.pos[slot] += 1
                    emitted.append((rid, tok))
                    if req.done:
                        self.state.active[slot] = False
                        del self.slot_of[rid]
            return emitted

    def run_to_completion(self, max_steps: int = 10_000, *,
                          strict: bool = True):
        """Step until every submitted request finishes.

        Raises :class:`IncompleteRunError` if ``max_steps`` is exhausted
        with requests still queued or active — requests are never
        silently dropped.  Pass ``strict=False`` to get the partial
        outputs back instead (in-flight requests stay resident and a
        further call can finish them)."""
        while (self._queue or self.state.active.any()) and max_steps:
            self.step()
            max_steps -= 1
        if self._queue or self.state.active.any():
            partial = {rid: list(req.out)
                       for rid, req in self.requests.items()}
            for req in self._queue:
                partial[req.rid] = list(req.out)
            if strict:
                raise IncompleteRunError(
                    queued=[r.rid for r in self._queue],
                    active=sorted(self.slot_of), partial=partial)
            return partial
        return {rid: req.out for rid, req in self.requests.items()}


def _cache_axes(pool_shape, tmpl_shape, slots: int, cache_len: int):
    """(slot_axis, cache_axis) of one pool cache leaf.

    ``tmpl_shape`` is the same leaf from a single-slot ``init_caches``
    with the same ``cache_len``: the slot axis is the first axis where
    the template is 1 and the pool is ``slots``-wide (tail leaves: 0;
    scan-stacked leaves: 1).  The cache-length axis is the first axis
    AFTER it sized ``cache_len`` — searching after the slot axis keeps
    a ``head_dim == cache_len`` coincidence from shadowing it; None for
    leaves without one (recurrent state, local-attention windows)."""
    s_ax = 0
    for i, (p, o) in enumerate(zip(pool_shape, tmpl_shape)):
        if o == 1 and p == slots:
            s_ax = i
            break
    c_ax = None
    for j in range(s_ax + 1, len(pool_shape)):
        if pool_shape[j] == cache_len:
            c_ax = j
            break
    return s_ax, c_ax


def _slot_write(pool, one, slot: int, slots: int):
    """Write a single-request cache leaf into slot ``slot`` of the pool.

    Handles both tail leaves (batch axis 0: pool (slots, L, ...), request
    (1, L, ...)) and scan-stacked leaves (batch axis 1: pool
    (n_sb, slots, L, ...), request (n_sb, 1, L, ...)); shorter dims are
    zero-padded (e.g. shorter prefill caches).  A source dim LONGER than
    the pool's raises :class:`CacheOverflowError` — silently cropping
    would throw away live KV state (the migrated-prefix boundary bug
    pinned in tests/test_engine.py)."""
    ax = 0
    for i, (p, o) in enumerate(zip(pool.shape, one.shape)):
        if o == 1 and p == slots:
            ax = i
            break
    target = list(pool.shape)
    target[ax] = 1
    over = [(i, a, b) for i, (a, b) in enumerate(zip(one.shape, target))
            if a > b]
    if over:
        raise CacheOverflowError(
            f"cache leaf {tuple(one.shape)} exceeds pool slot "
            f"{tuple(target)} on axes {[i for i, _, _ in over]}")
    pads = [(0, b - a) for a, b in zip(one.shape, target)]
    fitted = jnp.pad(one, pads).astype(pool.dtype)
    idx = [slice(None)] * pool.ndim
    idx[ax] = slice(slot, slot + 1)
    return pool.at[tuple(idx)].set(fitted)
