"""Continuous-batching serving engine over M2Q-quantized weights.

Slot-based: a fixed decode batch of B slots, each holding one request's KV
cache rows.  New requests prefill into free slots; every engine step decodes
one token for all live slots; finished requests free their slot immediately
(continuous batching — no head-of-line blocking on the longest request).

Admission runs on the shared scheduler core (serving.scheduler): ``submit``
enqueues onto a deadline-aware queue, and each step admits waiting requests
when the flush policy fires — immediately whenever slots are free with the
default ``max_delay_ms=0.0`` (regression-identical to the pre-scheduler
engine), or coalesced into bigger prefill batches when a positive deadline
is configured.  Queue latency, batch occupancy, and ragged-pad fractions
land in the unified ``ServeStats`` both serving engines share.

Device-resident decode loop: sampling (greedy AND temperature) runs inside
the jitted decode step, the pending next-token vector and the per-slot
output ring live on device, and the PRNG key threads through the jit — the
host never reads a token mid-request.  With an int8 KV cache the per-step
attention runs fully integer, and under the ``attn`` dispatch axis
(``DispatchConfig(attn=True)`` / ``REPRO_PALLAS_ATTN_DISPATCH``) it
executes as the fused ``kernels.decode_attn_int8`` Pallas kernel — one
VMEM pass per (batch, kv-head) instead of unfused XLA einsums.  The only device->host transfer is
one fetch of a request's finished token row when it completes (completion
itself is decided by host-side step counting, not by reading tokens).
Prefill is batched over ragged prompts: families that support right-padded
prompts with per-row lengths (``RAGGED_PREFILL``) admit every waiting
request in one call; recurrent families are bucketed by exact prompt length
so pad tokens never pollute their state.

With ``mesh=`` the engine runs sharded: params are placed per
``repro.dist.sharding.param_specs`` (QTensor payloads and scales co-shard),
the decode cache per ``cache_specs`` (batch rows over ``data``, attention
heads over ``model`` when divisible), and the decode step re-pins the cache
sharding every step so placements stay exactly on-spec.

Failure story (the fault-tolerance layer): executor exceptions are
contained PER BATCH — a failing prefill fails only its group's handles, a
failing decode step fails only the slots live in that step — and the
engine loop keeps serving everything else.  Per-request deadlines
(``submit(..., deadline_ms=)``) expire requests both queued and
mid-decode (freeing their slots), ``Handle.cancel()`` does the same on
the caller's initiative, and an ``OverloadPolicy`` bounds the admission
queue.  Kernel-dispatch failures degrade gracefully: the decode/prefill
steps run under a ``kernels.ops.FallbackGuard`` that retries a raising
Pallas step once on the XLA path (and latches the dispatch axes off).
Decode logits carry an in-graph finite check (a sticky per-slot flag,
read only at completion, preserving the one-d2h-per-completion
invariant): a NaN-poisoned request fails with ``NumericalError`` instead
of delivering garbage tokens.  A ``serving.faults.FaultInjector``
(``faults=`` or the ``REPRO_FAULT_SPEC`` env var) provokes all of the
above deterministically at the ``prefill``/``decode`` sites.

This is the serving analogue of the paper's deployment: weights are the
QTensor tree from core.quantize_model, executing the int8/APoT/packed-4bit
paths.
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
import time
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import ops as _kops
from ..models import get_model
from ..models.config import ArchConfig
from . import faults as _faults
from .batching import ServeStats, pow2_bucket
from .errors import NumericalError, RequestTimedOut
from .scheduler import (FlushPolicy, Handle, OverloadPolicy, Scheduler,
                        TIMED_OUT)


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray  # (P,) int32
    max_new_tokens: int = 16
    temperature: float = 0.0  # 0 = greedy
    out_tokens: Optional[List[int]] = None
    done: bool = False
    handle: Optional[Handle] = None  # scheduler future (resolves at finish)
    stream: bool = False             # push tokens through the handle
    preemptible: bool = False        # slot may be evicted for higher prio
    # preemption continuation state (restart-from-prefix): tokens decoded
    # by earlier incarnations — the final result is out_prefix + the
    # current incarnation's out_tokens
    out_prefix: List[int] = dataclasses.field(default_factory=list)
    preemptions: int = 0


@dataclasses.dataclass
class EngineStats(ServeStats):
    """Unified ServeStats + the token engine's decode-loop counters."""

    steps: int = 0
    decoded_tokens: int = 0
    prefills: int = 0
    prefill_batches: int = 0
    finished: int = 0
    preemptions: int = 0       # slot evictions (restart-from-prefix)
    streamed_tokens: int = 0   # tokens pushed through streaming handles


class Engine:
    def __init__(self, cfg: ArchConfig, params, max_batch: int = 4,
                 max_len: int = 256, seed: int = 0,
                 max_delay_ms: float = 0.0,
                 dispatch: Optional[_kops.DispatchConfig] = None,
                 mesh=None,
                 clock: Callable[[], float] = time.monotonic,
                 overload: Optional[OverloadPolicy] = None,
                 faults: Optional[_faults.FaultInjector] = None,
                 check_numerics: bool = True,
                 debug_numerics: Optional[bool] = None):
        # scoped kernels.ops.DispatchConfig pinning kernel dispatch for the
        # engine's prefill/decode traces (None inherits env/backend
        # default); the attn axis steers the int8-KV decode-attention
        # kernel in every decode step
        self.dispatch = dispatch
        self.cfg = cfg
        self.model = get_model(cfg)
        self.B = max_batch
        self.T = max_len
        self.slots: List[Optional[Request]] = [None] * max_batch
        self.stats = EngineStats()
        if max_delay_ms is None:
            # None (the vision explicit-flush mode) would leave a sub-
            # max_batch queue waiting forever: the token engine has no
            # drain() path, so admission MUST have a deadline
            raise ValueError(
                "token engine admission needs a deadline: use "
                "max_delay_ms=0.0 (admit whenever slots free) or > 0 "
                "(coalesce prefills), not None")
        # admission queue on the shared scheduler core; max_delay_ms=0.0
        # admits whenever slots are free (the classic behavior), >0
        # coalesces prefills until the batch fills or the deadline fires.
        # overload= bounds it (QueueFullError / shed-oldest); faults= (or
        # REPRO_FAULT_SPEC) provokes failures at the prefill/decode sites
        self.faults = faults if faults is not None else _faults.from_env()
        self.check_numerics = check_numerics
        # opt-in PRE-quantization numerics check (constructor arg, or the
        # REPRO_DEBUG_NUMERICS env var when the arg is None): every decode
        # step also scans the inexact cache leaves — on a quantized engine
        # the logits-only check can miss a cache NaN laundered through
        # activation quantization (NaN.astype(int8) is finite), but the
        # dynamic per-row KV scales (max|x|/127) stay f32 and DO carry the
        # NaN.  Costs a full cache read per step; debug posture only.
        if debug_numerics is None:
            debug_numerics = os.environ.get(
                "REPRO_DEBUG_NUMERICS", "").strip().lower() in (
                    "1", "true", "on", "yes")
        self.debug_numerics = bool(debug_numerics)
        self.scheduler = Scheduler(
            policy=FlushPolicy(max_batch=max_batch,
                               max_delay_ms=max_delay_ms),
            stats=self.stats, clock=clock, overload=overload)
        # retry-once-on-XLA guard around the kernel-dispatched steps (no
        # finite check here: that would force a device sync per decode
        # step — numerics ride the in-graph sticky flag instead)
        self.fallback_guard = _kops.FallbackGuard(check_finite=False)
        # real-clock time step() last ENTERED, regardless of the injected
        # scheduler clock: the supervision layer's liveness signal (a
        # virtual-clock engine still beats wall-clock time while stepped)
        self.heartbeat: Optional[float] = None
        self._ragged = bool(getattr(self.model, "RAGGED_PREFILL", False))
        self.cache = self.model.init_cache(cfg, max_batch, max_len,
                                           dtype=jnp.float32)
        self.mesh = mesh
        self._cache_shardings = None
        if mesh is not None:
            params, self.cache = self._shard(params, self.cache, mesh)
        self.params = params
        # device-resident decode state
        self.key = jax.random.PRNGKey(seed)
        self._pending = jnp.zeros((max_batch,), jnp.int32)
        self._temps = jnp.zeros((max_batch,), jnp.float32)
        self._outbuf = jnp.zeros((max_batch, max_len), jnp.int32)
        self._counts = jnp.zeros((max_batch,), jnp.int32)
        # sticky per-slot non-finite-logits flag, accumulated IN-GRAPH by
        # the decode/prefill steps and read back only at completion (the
        # one allowed d2h) — a poisoned request fails with NumericalError
        # instead of delivering garbage tokens
        self._nonfinite = jnp.zeros((max_batch,), bool)
        # host mirror of per-slot emitted-token counts (drives completion
        # without reading token values back)
        self._emitted = [0] * max_batch
        # ``fallback`` is STATIC: dispatch is resolved at trace time, so
        # the FallbackGuard's XLA retry needs its own trace, not a stale
        # kernel-path trace replayed under a different ambient scope
        self._decode_step = jax.jit(self._decode_step_impl,
                                    static_argnames=("fallback",))
        self._prefill_sample = jax.jit(self._prefill_sample_impl,
                                       static_argnames=("fallback",))
        self._prefill_sample_ragged = jax.jit(
            self._prefill_sample_ragged_impl, static_argnames=("fallback",))

    def _shard(self, params, cache, mesh):
        """Place params/cache per dist.sharding (decode caches shard over
        the mesh; QTensor payload+scale children co-shard by spec)."""
        from ..dist import sharding as shd
        params = jax.device_put(
            params, shd.shardings_from_specs(shd.param_specs(params, mesh),
                                             mesh))
        self._cache_shardings = shd.shardings_from_specs(
            shd.cache_specs(cache, mesh, shard_model=True), mesh)
        return params, jax.device_put(cache, self._cache_shardings)

    # -- request API ---------------------------------------------------------
    @property
    def queue(self) -> List[Request]:
        """Requests waiting for admission (FIFO), via the scheduler."""
        return self.scheduler.pending_payloads()

    def submit(self, prompt, max_new_tokens: int = 16,
               temperature: float = 0.0,
               deadline_ms: Optional[float] = None,
               priority: int = 0,
               stream: bool = False,
               on_token: Optional[Callable[[int], None]] = None,
               preemptible: bool = False) -> Request:
        """Enqueue one request; returns a :class:`Request` whose
        ``.handle`` resolves (or fails) at completion.

        ``deadline_ms``: optional per-request deadline — the request
        TIMES OUT (handle state ``TIMED_OUT``, slot freed) if it has not
        completed within that many ms of submission, queued or mid-decode.

        ``priority``: higher admits first (the scheduler's priority
        queue; FIFO within a class).  ``preemptible``: this request's
        decode slot may be EVICTED when a strictly-higher-priority
        request is due and no slot is free — it restarts from prefix
        (prompt + tokens so far) at the back of its class, keeping every
        already-decoded token.  ``stream=True`` (or passing ``on_token``,
        which implies it) delivers each decoded token incrementally
        through the handle — ``handle.tokens()`` / the callback — at the
        cost of one extra device->host read per decode step shared by
        ALL streaming slots (non-streaming requests keep the strict
        one-transfer-per-completion invariant).  Streamed tokens are
        pushed BEFORE the completion-time numerics check: the handle's
        terminal state says whether the stream is trustworthy.

        Raises ``ValueError`` on malformed payloads — validated UP FRONT
        so bad inputs fail here with a clear message, not deep inside a
        jitted prefill: non-1-D prompts, non-integer dtypes (embeddings
        or logits passed by mistake), token ids outside the vocab, empty
        prompts, ``max_new_tokens < 1``, or a request that cannot fit
        ``max_len``.  Raises ``QueueFullError`` when a bounded queue
        rejects the submit (see ``OverloadPolicy``).
        """
        arr = np.asarray(prompt)
        if arr.ndim != 1:
            raise ValueError(
                f"prompt must be a 1-D vector of token ids, got shape "
                f"{arr.shape}")
        if arr.size and not np.issubdtype(arr.dtype, np.integer):
            raise ValueError(
                f"prompt dtype must be integer token ids, got {arr.dtype} "
                "— passing embeddings/logits (or float-typed ids) would "
                "be silently truncated")
        if arr.size and (int(arr.min()) < 0
                         or int(arr.max()) >= self.cfg.vocab_size):
            raise ValueError(
                f"prompt token ids must be in [0, {self.cfg.vocab_size}), "
                f"got range [{int(arr.min())}, {int(arr.max())}]")
        prompt = arr.astype(np.int32)
        if len(prompt) == 0:
            raise ValueError("empty prompt: prefill needs at least one token")
        if max_new_tokens < 1:
            # a zero/negative budget would still burn a full prefill+sample
            # (the first token IS sampled at prefill) and retire with empty
            # output — reject instead of doing work the caller threw away
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens} (every "
                "admitted request decodes at least its prefill-sampled "
                "first token)")
        if len(prompt) + max_new_tokens > self.T:
            # the KV cache and the device output ring are both max_len wide;
            # silently clamping would truncate/corrupt the decoded stream
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens ({max_new_tokens})"
                f" exceeds max_len ({self.T})")
        req = Request(uid=0, prompt=prompt, max_new_tokens=max_new_tokens,
                      temperature=temperature, out_tokens=[],
                      stream=bool(stream) or on_token is not None,
                      preemptible=bool(preemptible))
        req.handle = self.scheduler.submit(req, deadline_ms=deadline_ms,
                                           priority=priority,
                                           on_token=on_token)
        req.uid = req.handle.uid
        return req

    def _dispatch_scope(self):
        """The kernel-dispatch pin and the engine's mesh, for its traces
        (kernels launch per device under a mesh: ``ops.kernel_mesh``)."""
        scope = contextlib.ExitStack()
        if self.dispatch is not None:
            scope.enter_context(_kops.dispatch(self.dispatch))
        scope.enter_context(_kops.kernel_mesh(self.mesh))
        return scope

    # -- jitted cores --------------------------------------------------------
    def _sample_tokens(self, logits, key, temps):
        """(B, V_padded) logits -> (B,) int32 tokens, fully in-graph."""
        lg = logits[:, : self.cfg.vocab_size].astype(jnp.float32)
        greedy = jnp.argmax(lg, axis=-1).astype(jnp.int32)
        safe_t = jnp.maximum(temps, 1e-6)[:, None]
        keys = jax.random.split(key, lg.shape[0])
        drawn = jax.vmap(jax.random.categorical)(keys, lg / safe_t)
        return jnp.where(temps > 0, drawn.astype(jnp.int32), greedy)

    def _fallback_scope(self, fallback: bool):
        """``fallback=True`` (STATIC) pins the whole step to the XLA path
        for the FallbackGuard's retry trace — all three dispatch axes off,
        beating any ambient scope/env/latch (dispatch resolves at trace
        time, and this scope wraps the traced body)."""
        return (_kops.dispatch(dense=False, conv=False, attn=False)
                if fallback else contextlib.nullcontext())

    def _row_nonfinite(self, logits):
        """(B, V_padded) last-position logits -> (B,) bool: row holds any
        NaN/Inf inside the real vocab (in-graph; no host sync)."""
        lg = logits[:, : self.cfg.vocab_size].astype(jnp.float32)
        return ~jnp.all(jnp.isfinite(lg), axis=-1)

    def _cache_nonfinite(self, cache):
        """(B,) bool: any NaN/Inf in a slot's inexact cache rows (in-graph;
        batch axis 1 per the ``_write_slots`` convention).  Int payloads
        are skipped — after quantization they are finite by construction;
        it is the f32 leaves (float caches, per-row KV scales, recurrent
        states) that still carry a pre-quantization NaN."""
        bad = jnp.zeros((self.B,), bool)
        for leaf in jax.tree.leaves(cache):
            if leaf.ndim < 2 or not jnp.issubdtype(leaf.dtype, jnp.inexact):
                continue
            axes = tuple(a for a in range(leaf.ndim) if a != 1)
            bad = bad | ~jnp.all(jnp.isfinite(leaf), axis=axes)
        return bad

    def _decode_step_impl(self, params, cache, pending, outbuf, counts,
                          temps, live, nonfinite, key, fallback=False):
        with self._fallback_scope(fallback):
            key, k_s = jax.random.split(key)
            logits, cache = self.model.decode_step(self.cfg, params, cache,
                                                   pending[:, None])
            # sticky numerics flag: once a live slot's logits go non-finite
            # the bit stays set until the slot retires (read only at
            # completion — the d2h-per-completion invariant holds)
            nonfinite = nonfinite | (self._row_nonfinite(logits[:, 0]) & live)
            if self.debug_numerics:
                # opt-in pre-quantization check: a cache NaN that activation
                # quantization would launder into finite logits still trips
                # the sticky flag here (see REPRO_DEBUG_NUMERICS)
                nonfinite = nonfinite | (self._cache_nonfinite(cache) & live)
            tok = self._sample_tokens(logits[:, 0], k_s, temps)
            tok = jnp.where(live, tok, pending)
            b = jnp.arange(self.B)
            at = jnp.minimum(counts, self.T - 1)
            outbuf = outbuf.at[b, at].set(
                jnp.where(live, tok, outbuf[b, at]))
            counts = counts + live.astype(jnp.int32)
            if self._cache_shardings is not None:
                # pin the cache's dist.sharding placement through the step
                # so the sharded decode loop stays exactly on-spec
                cache = jax.tree.map(jax.lax.with_sharding_constraint, cache,
                                     self._cache_shardings)
            return cache, tok, outbuf, counts, nonfinite, key

    def _prefill_sample_impl(self, params, slot_cache, tokens, temps, key,
                             fallback=False):
        with self._fallback_scope(fallback):
            logits, slot_cache = self.model.prefill(self.cfg, params,
                                                    slot_cache, tokens)
            tok = self._sample_tokens(logits[:, -1], key, temps)
            return tok, slot_cache, self._row_nonfinite(logits[:, -1])

    def _prefill_sample_ragged_impl(self, params, slot_cache, tokens,
                                    lengths, temps, key, fallback=False):
        with self._fallback_scope(fallback):
            logits, slot_cache = self.model.prefill(self.cfg, params,
                                                    slot_cache, tokens,
                                                    lengths=lengths)
            tok = self._sample_tokens(logits[:, -1], key, temps)
            return tok, slot_cache, self._row_nonfinite(logits[:, -1])

    # -- internals -----------------------------------------------------------
    def _write_slots(self, slots: List[int], group_cache):
        """Copy an (n, ...) batched prefill cache into the engine cache."""
        idx = jnp.asarray(slots, jnp.int32)

        def put(dst, src):
            if dst.ndim == 1:  # lengths (B,)
                return dst.at[idx].set(src)
            return dst.at[:, idx].set(src)

        self.cache = jax.tree.map(put, self.cache, group_cache)
        if self._cache_shardings is not None:
            # eager .at[].set left the placement to XLA; re-pin to spec
            self.cache = jax.device_put(self.cache, self._cache_shardings)

    def _admit(self):
        # Free slots and the due-check are recomputed on every pass: the
        # in-loop _finish_done() (max_new_tokens==1 completing at prefill)
        # frees slots that queued requests can take within the SAME admit
        # call — computing ``free`` once left them idle until the next
        # step.  With the default max_delay_ms=0.0 the scheduler is due
        # whenever anything is pending (classic admit-on-free-slot); a
        # positive deadline holds admission to coalesce prefill batches.
        while True:
            free = [i for i, r in enumerate(self.slots) if r is None]
            if not free:
                if not self._maybe_preempt():
                    return
                continue  # the evicted slot is free for the due head
            reason = self.scheduler.due()
            if reason is None:
                return
            cands = self.scheduler.peek(len(free))
            if self._ragged:
                group = list(cands)
            else:  # exact-length bucket: recurrent states must not see
                # padding; one bucket per pass, the rest re-enter next pass
                by_len: Dict[int, List[Handle]] = {}
                for h in cands:
                    by_len.setdefault(len(h.payload.prompt), []).append(h)
                group = next(iter(by_len.values()))
            group = self.scheduler.pop(group, reason)
            if not group:
                continue  # whole group cancelled/expired while queued
            try:
                self._prefill_group(free[: len(group)], group)
            except Exception as e:  # noqa: BLE001 — per-batch containment
                # a failing prefill (executor bug, injected fault, a raise
                # surviving the guard's XLA retry) fails ONLY this group's
                # handles; no slot was written, the engine keeps serving
                for h in group:
                    h.set_exception(e)

    def _maybe_preempt(self) -> bool:
        """With every slot occupied: evict ONE preemptible lower-priority
        decode if a strictly-higher-priority request is due at the head
        of the queue.  Victim = lowest priority first, then most tokens
        emitted (the continuation with the least decoding left — it
        rejoins and retires soonest once pressure passes).
        Returns True if a slot was freed."""
        if self.scheduler.due() is None:
            return False
        head = self.scheduler.peek(1)
        if not head:
            return False
        want = head[0].priority
        victims = []
        for slot, req in enumerate(self.slots):
            if (req is None or not req.preemptible or req.handle is None
                    or req.handle.done()
                    or req.handle.priority >= want):
                continue
            victims.append((req.handle.priority, -self._emitted[slot], slot))
        if not victims:
            return False
        self._preempt_slot(min(victims)[2])
        return True

    def _preempt_slot(self, slot: int) -> None:
        """Evict one in-flight decode, restart-from-prefix: fold the
        tokens decoded so far into the request's prompt (prompt grows,
        ``max_new_tokens`` shrinks — their sum is invariant, so the
        ``<= max_len`` admission check still holds) and requeue the SAME
        handle at the back of its priority class.  One device->host read
        of the victim's token row per eviction (preemption is rare and
        off the per-step hot path).  A victim whose sticky numerics flag
        already tripped is failed instead — releasing its slot would
        clear the flag and the restart would launder poisoned tokens
        into the continuation's prompt."""
        req = self.slots[slot]
        h = req.handle
        emitted = self._emitted[slot]
        if self.check_numerics and bool(
                jax.device_get(self._nonfinite[slot])):
            h.set_exception(NumericalError(
                f"request {h.uid} produced non-finite logits during "
                "decode (caught at preemption); its tokens are not "
                "trustworthy and were not delivered"))
            self._release_slot(slot)
            return
        toks = np.asarray(jax.device_get(self._outbuf[slot, :emitted]))
        decoded = [int(t) for t in toks]
        req.out_prefix.extend(decoded)
        req.prompt = np.concatenate(
            [req.prompt, toks.astype(np.int32)])
        # emitted < max_new_tokens always holds here (a slot at its
        # budget retired in _finish_done), so the remainder stays >= 1
        req.max_new_tokens -= emitted
        req.preemptions += 1
        self.stats.preemptions += 1
        self._release_slot(slot)
        self.scheduler.requeue(h)

    def _prefill_group(self, gslots: List[int], handles: List[Handle]):
        greqs = [h.payload for h in handles]
        lens = np.asarray([len(r.prompt) for r in greqs], np.int32)
        pmax = int(lens.max())
        if self._ragged:
            # bucket the padded length to a power of two (capped at
            # max_len): bounds XLA recompiles of the prefill graph to
            # O(B * log T) shape variants instead of one per distinct
            # prompt length; lengths mask the extra pad columns
            pmax = pow2_bucket(pmax, 8, self.T)
        toks = np.zeros((len(greqs), pmax), np.int32)
        for i, r in enumerate(greqs):
            toks[i, : len(r.prompt)] = r.prompt
        sc = self.model.init_cache(self.cfg, len(greqs), self.T,
                                   dtype=jnp.float32)
        temps = jnp.asarray([r.temperature for r in greqs], jnp.float32)
        self.key, k = jax.random.split(self.key)
        act = (self.faults.on_call("prefill")
               if self.faults is not None else None)
        with self._dispatch_scope():
            if act is not None:
                act.fire()  # raises/delays land BEFORE any state mutates
            if self._ragged:
                first, sc, bad = self.fallback_guard.run(
                    self._prefill_sample_ragged, self.params, sc,
                    jnp.asarray(toks), jnp.asarray(lens), temps, k)
            else:
                first, sc, bad = self.fallback_guard.run(
                    self._prefill_sample, self.params, sc,
                    jnp.asarray(toks), temps, k)
        if act is not None and act.poison:
            # simulated silent corruption of the group's prefill logits:
            # flag row 0 — ONE request fails with NumericalError at
            # completion, its groupmates are untouched
            bad = bad.at[0].set(True)
        self._write_slots(gslots, sc)
        idx = jnp.asarray(gslots, jnp.int32)
        self._pending = self._pending.at[idx].set(first)
        self._temps = self._temps.at[idx].set(temps)
        self._outbuf = self._outbuf.at[idx, 0].set(first)
        self._counts = self._counts.at[idx].set(1)
        self._nonfinite = self._nonfinite.at[idx].set(bad)
        for s, r in zip(gslots, greqs):
            self.slots[s] = r
            self._emitted[s] = 1
        self.stats.prefills += len(greqs)
        self.stats.prefill_batches += 1
        if any(r.stream for r in greqs):
            # streamers pay one extra d2h per prefill group for their
            # prefill-sampled first token; non-streamers keep the strict
            # one-transfer-per-completion invariant
            fv = np.asarray(jax.device_get(first))
            for i, (r, h) in enumerate(zip(greqs, handles)):
                if r.stream and h.push_token(int(fv[i])):
                    self.stats.streamed_tokens += 1
        # unified queue-level accounting: real prompt tokens vs the padded
        # (n, pmax) prefill actually executed
        self.stats.record_batch(items=int(lens.sum()),
                                padded=int(len(greqs) * pmax - lens.sum()),
                                capacity=self.B * pmax)
        self._finish_done()  # max_new_tokens == 1 finishes at prefill

    def _release_slot(self, slot: int) -> None:
        """Free a slot mid-flight or at retirement: drop the host request
        and clear the slot's sticky numerics flag so the next occupant
        starts clean (its cache rows are overwritten at prefill)."""
        self.slots[slot] = None
        self._emitted[slot] = 0
        self._nonfinite = self._nonfinite.at[slot].set(False)

    def _sweep_slots(self) -> None:
        """Retire in-flight requests that went terminal without a result:
        caller cancellation (``Handle.cancel()``), and per-request deadline
        expiry — deadlines fire MID-DECODE too, not only while queued, so
        a stuck/slow request cannot squat its slot past its budget."""
        # queued expiry first: _admit only consults due() when a slot is
        # free, so without this a full engine would leave expired queued
        # requests PENDING until something retires
        self.scheduler.expire()
        now = self.scheduler.now()
        for slot, req in enumerate(self.slots):
            if req is None or req.handle is None:
                continue
            h = req.handle
            if (not h.done() and h.deadline is not None
                    and now >= h.deadline):
                h.set_exception(
                    RequestTimedOut(
                        f"request {h.uid} timed out mid-decode after "
                        f"{self._emitted[slot]} token(s); freeing its slot"),
                    state=TIMED_OUT)
            if h.done():
                self._release_slot(slot)

    def _finish_done(self):
        """Retire completed slots; the ONLY per-request device->host reads
        (the slot's sticky numerics flag, then — when it is clean — the
        finished token row)."""
        for slot, req in enumerate(self.slots):
            if req is None or self._emitted[slot] < req.max_new_tokens:
                continue
            h = req.handle
            if self.check_numerics and bool(
                    jax.device_get(self._nonfinite[slot])):
                # the in-graph sticky flag caught NaN/Inf logits somewhere
                # in this request's decode: fail it rather than deliver
                # garbage tokens sampled from poisoned logits
                req.done = True
                if h is not None:
                    h.set_exception(NumericalError(
                        f"request {h.uid} produced non-finite logits "
                        "during decode (NaN/Inf); its tokens are not "
                        "trustworthy and were not delivered"))
                self._release_slot(slot)
                continue
            toks = np.asarray(
                jax.device_get(self._outbuf[slot, : req.max_new_tokens]))
            # out_prefix carries tokens from pre-preemption incarnations;
            # the delivered result is always the full decoded sequence
            req.out_tokens = req.out_prefix + [int(t) for t in toks]
            req.done = True
            delivered = True
            if h is not None:
                # a late result into a handle the caller already cancelled
                # (or that timed out this very step) is dropped by the
                # state machine — don't double-count it as finished
                delivered = h.set_result(req.out_tokens)
            if delivered:
                self.stats.finished += 1
            self._release_slot(slot)

    def step(self) -> int:
        """Admit + one decode step for all live slots. Returns #live.

        Failure containment: a raising decode step (executor bug or
        injected fault) fails ONLY the slots live in that step — their
        handles get the exception, their slots free — and the engine keeps
        serving the queue.  The step itself never raises.
        """
        self.heartbeat = time.monotonic()
        self._sweep_slots()  # cancellations + mid-decode deadline expiry
        self._admit()
        live_mask = np.asarray([r is not None for r in self.slots], bool)
        live = [i for i in range(self.B) if live_mask[i]]
        if not live:
            return 0
        act = (self.faults.on_call("decode")
               if self.faults is not None else None)
        try:
            if act is not None:
                act.fire()
                if act.poison:
                    self._poison_slot(live[0])
            with self._dispatch_scope():
                (self.cache, self._pending, self._outbuf, self._counts,
                 self._nonfinite, self.key) = self.fallback_guard.run(
                    self._decode_step, self.params, self.cache,
                    self._pending, self._outbuf, self._counts, self._temps,
                    jnp.asarray(live_mask), self._nonfinite, self.key)
        except Exception as e:  # noqa: BLE001 — per-batch containment
            for slot in live:
                req = self.slots[slot]
                if req is not None and req.handle is not None:
                    req.handle.set_exception(e)
                self._release_slot(slot)
            return 0
        self.stats.steps += 1
        self.stats.decoded_tokens += len(live)
        for slot in live:
            self._emitted[slot] += 1
        self._stream_live(live)
        self._finish_done()
        return len(live)

    def _stream_live(self, live: List[int]) -> None:
        """Push this step's sampled token into every live STREAMING
        slot's handle.  Costs one device->host read of the pending-token
        vector per step, shared across all streaming slots, and nothing
        at all when no live slot streams — the one-transfer-per-
        completion invariant is intact for non-streaming traffic."""
        streamers = [
            s for s in live
            if self.slots[s] is not None and self.slots[s].stream
            and self.slots[s].handle is not None]
        if not streamers:
            return
        pend = np.asarray(jax.device_get(self._pending))
        for s in streamers:
            if self.slots[s].handle.push_token(int(pend[s])):
                self.stats.streamed_tokens += 1

    def _poison_slot(self, slot: int) -> None:
        """NaN-poison ONE slot's KV-cache rows (the fault injector's
        ``nan@decode`` site): that single request's logits go non-finite,
        the sticky flag catches it, and it alone fails with
        ``NumericalError`` — its batchmates decode on unharmed."""
        def poison(leaf):
            if not jnp.issubdtype(leaf.dtype, jnp.inexact):
                return leaf
            if leaf.ndim == 1:  # per-slot lengths etc.
                return leaf
            # batch axis convention matches _write_slots: axis 1 for the
            # (layers, B, ...) stacked cache leaves
            return leaf.at[:, slot].set(jnp.nan)
        self.cache = jax.tree.map(poison, self.cache)

    def run(self, max_steps: int = 10_000) -> EngineStats:
        for _ in range(max_steps):
            if self.scheduler.pending == 0 and all(
                    s is None for s in self.slots):
                break
            if self.step() == 0 and self.scheduler.pending \
                    and self.scheduler.clock is time.monotonic:
                # nothing live and the queue not yet due (max_delay_ms > 0
                # holding admission): sleep toward the deadline instead of
                # hot-spinning the step budget away.  Only on the REAL
                # clock — sleeping cannot advance an injected virtual
                # clock, whose driver steps the engine itself
                nd = self.scheduler.next_deadline()
                if nd is not None:
                    delay = nd - self.scheduler.clock()
                    if delay > 0:
                        time.sleep(min(delay, 1e-3))
        return self.stats
