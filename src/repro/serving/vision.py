"""Batched vision inference (images in, logits out) over M2Q backbones.

The token engine (serving.engine) is slot-structured because decode is
stateful; image classification is stateless, so its serving shape is a
*thin executor plugged into the shared scheduler core*
(serving.scheduler): ``submit()`` enqueues one image and returns a
:class:`~repro.serving.scheduler.Handle` immediately; the request executes
when the flush policy fires — the batch fills to ``max_batch``, the oldest
request's age exceeds ``max_delay_ms`` (checked by :meth:`poll`), or an
explicit :meth:`flush` drains the queue — and the handle's ``result()``
yields that image's logits row.

Each executed batch pads up to a power-of-two bucket (shared
``batching.pow2_bucket`` — the same trick the token engine applies to
ragged prefill lengths) before running ONE jitted forward.  With ``mesh=``
the engine runs data-parallel sharded execution: params are placed by
``repro.dist.sharding.param_specs``, the bucket floor rises to the data
axis size so every executed batch shards evenly over ``batch_specs``.

With QTensor params (core.quantize_model) the jitted forward executes the
quantized conv/matmul hot path end to end: stride-1 1x1 PWConvs run the
fused m2q/int8 matmul kernels, depthwise filters the packed-w4 conv kernel
(kernels.ops.conv_dispatch_enabled), with the pure-XLA QTensor paths as
fallback — no f32 dequantized-weight convolutions.

Called directly, every call returns with its batch delivered.  Under a
``ServingDaemon`` a batch is split in two halves (:class:`Completions`):
the flushing thread stacks and copies it to the device and returns; the
daemon's completion thread waits on it, fetches it and delivers it, so
the host prepares the next batch while the device runs this one.

Failure story (the fault-tolerance layer): executor exceptions fail ONLY
the batch that was executing (the scheduler core contains them) and the
engine keeps serving.  The jitted forward runs under a
``kernels.ops.FallbackGuard``: a raising or NaN-producing kernel-dispatched
forward is retried once on the XLA path (and the dispatch axes latch off
process-wide).  Delivered logits are finite-checked PER ROW — a poisoned
image fails alone with ``NumericalError`` while its batchmates get their
results.  ``submit(..., deadline_ms=)`` expires queued requests,
``OverloadPolicy`` bounds the queue, and a ``serving.faults.FaultInjector``
(``faults=`` or ``REPRO_FAULT_SPEC``) provokes all of it deterministically
at the ``vision`` / ``vision.kernel`` / ``executor`` sites.
"""
from __future__ import annotations

import collections
import contextlib
import contextvars
import dataclasses
import threading
import time
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import tracing
from ..kernels import ops as _kops
from ..models import get_model
from ..models.config import ArchConfig
from . import faults as _faults
from .batching import ServeStats, pow2_bucket
from .errors import NumericalError
from .scheduler import DONE, FlushPolicy, Handle, OverloadPolicy, Scheduler


@dataclasses.dataclass
class VisionStats(ServeStats):
    """Unified ServeStats + the vision-historical field names."""

    @property
    def images(self) -> int:
        return self.items

    @property
    def padded_images(self) -> int:
        return self.padded_items


class VisionEngine:
    """Deadline-batched classifier: submit images, poll (or flush) for
    logits delivered through per-request handles."""

    def __init__(self, cfg: ArchConfig, params, max_batch: int = 64,
                 min_bucket: int = 1,
                 max_delay_ms: Optional[float] = None,
                 dispatch: Optional[_kops.DispatchConfig] = None,
                 mesh=None,
                 clock: Callable[[], float] = time.monotonic,
                 overload: Optional[OverloadPolicy] = None,
                 faults: Optional[_faults.FaultInjector] = None,
                 check_numerics: bool = True):
        if max_batch < 1:
            raise ValueError("max_batch must be >= 1")
        self.cfg = cfg
        self.model = get_model(cfg)
        self.B = max_batch
        self.min_bucket = max(1, min_bucket)
        self.stats = VisionStats()
        self.mesh = mesh
        self._batch_spec = None
        if mesh is not None:
            params = self._shard(params, mesh)
        self.params = params
        # faults= (or REPRO_FAULT_SPEC) provokes failures at the vision /
        # vision.kernel / executor sites; overload= bounds the queue
        self.faults = faults if faults is not None else _faults.from_env()
        self.check_numerics = check_numerics
        self.scheduler = Scheduler(
            policy=FlushPolicy(max_batch=max_batch,
                               max_delay_ms=max_delay_ms),
            executor=self._execute, stats=self.stats, clock=clock,
            overload=overload, faults=self.faults)
        # retry-once-on-XLA guard around the kernel-dispatched forward; the
        # finite check here is cheap (the vision path syncs per batch
        # anyway) so a NaN-producing kernel also degrades to XLA
        self.fallback_guard = _kops.FallbackGuard(
            check_finite=True, faults=self.faults, site="vision.kernel",
            span_prefix="vision")
        # real-clock time poll() last entered (supervision liveness signal,
        # independent of any injected virtual scheduler clock)
        self.heartbeat: Optional[float] = None
        # ``fallback`` is STATIC: the guard's XLA retry needs its own
        # trace, not the kernel-path trace replayed under another scope
        self._fwd = jax.jit(self._fwd_impl, static_argnames=("fallback",))
        # pin kernel dispatch for every trace this engine owns (scoped
        # kernels.ops.DispatchConfig; None inherits env/backend defaults)
        self.dispatch = dispatch

    def _shard(self, params, mesh):
        """Place params per dist.sharding and raise the bucket floor to the
        data-axis size so every pow2 batch shards evenly."""
        from ..dist import sharding as shd
        from jax.sharding import NamedSharding, PartitionSpec as P
        axes = dict(mesh.shape)
        data = int(axes.get("data", 1))
        if data > 1:
            if data & (data - 1):
                raise ValueError(
                    f"data axis size {data} is not a power of two; pow2 "
                    "batch buckets cannot shard evenly over it")
            if self.B % data:
                raise ValueError(
                    f"max_batch ({self.B}) must be divisible by the data "
                    f"axis size ({data}) for sharded execution")
            self.min_bucket = max(self.min_bucket, data)
            self._batch_spec = NamedSharding(mesh, P("data", None, None, None))
        return jax.device_put(
            params, shd.shardings_from_specs(shd.param_specs(params, mesh),
                                             mesh))

    def _dispatch_scope(self):
        """The kernel-dispatch pin and the engine's mesh, for its traces
        (kernels launch per device under a mesh: ``ops.kernel_mesh``)."""
        scope = contextlib.ExitStack()
        if self.dispatch is not None:
            scope.enter_context(_kops.dispatch(self.dispatch))
        scope.enter_context(_kops.kernel_mesh(self.mesh))
        return scope

    def _fwd_impl(self, params, images, fallback=False):
        # fallback=True (static) pins the retry trace to the XLA path —
        # all dispatch axes off, beating any ambient scope/env/latch
        scope = (_kops.dispatch(dense=False, conv=False, attn=False)
                 if fallback else contextlib.nullcontext())
        with scope:
            return self.model.forward(self.cfg, params, images)

    def bucket(self, n: int) -> int:
        """Smallest power-of-two >= n (floored at min_bucket, capped at
        max_batch) — the batch shape actually compiled and executed."""
        return pow2_bucket(n, self.min_bucket, self.B)

    # -- execution core ------------------------------------------------------
    def _run_batch(self, images: np.ndarray, bucket: int) -> "_Step":
        """Pad ``images`` (n <= bucket) up to ``bucket`` rows and put them
        on the device; returns the batch's :class:`_Step`, which
        ``np.asarray`` turns into the n real logits rows (launching and
        waiting on the jitted forward where nothing launched it yet)."""
        n = images.shape[0]
        pad = bucket - n
        if pad:
            with tracing.span("vision.assemble"):
                images = np.concatenate(
                    [images, np.zeros((pad,) + images.shape[1:],
                                      np.float32)])
        with tracing.span("vision.put"):
            x = jnp.asarray(images)
            if self._batch_spec is not None:
                x = jax.device_put(x, self._batch_spec)
        return _Step(self, x, n, bucket)

    def _prepare(self, handles: List[Handle]):
        """The first half of a batch, on the thread that flushes it: the
        ``vision`` fault site, the stack and the put.  Returns ``(step,
        act)`` for :meth:`_complete`."""
        act = (self.faults.on_call("vision")
               if self.faults is not None else None)
        if act is not None:
            act.fire()  # raises/delays before any work runs
        with tracing.span("vision.assemble"):
            imgs = np.stack([h.payload for h in handles]).astype(np.float32)
        return self._run_batch(imgs, self.bucket(len(handles))), act

    def _complete(self, handles: List[Handle], step, act) -> None:
        """The second half: wait on the forward (launching it first where
        nothing did), fetch, poison where ``act`` says, and deliver."""
        out = np.asarray(step)
        if act is not None and act.poison:
            # simulated silent corruption of the batch's outputs: poison
            # ONE row — that request fails alone, batchmates deliver
            out = out.copy()
            out[0] = np.nan
        with tracing.span("vision.deliver"):
            for i, (h, row) in enumerate(zip(handles, out)):
                if self.check_numerics and not np.all(np.isfinite(row)):
                    h.set_exception(NumericalError(
                        f"request {h.uid}: non-finite logits from the "
                        f"vision forward (row {i} of the executed "
                        "batch); its result was not delivered"))
                else:
                    h.set_result(row)

    def _execute(self, handles: List[Handle], reason: str) -> None:
        """Scheduler executor: one flushed batch -> per-handle logits.

        Per-ROW numerics containment: rows of the executed batch holding
        NaN/Inf fail their handle alone with ``NumericalError``; the rest
        of the batch delivers normally.  An exception out of here (an
        injected ``vision``-site fault, an OOM, a raise surviving the
        guard's XLA retry) is contained by the scheduler core: it fails
        this batch's handles and the serving loop keeps running.

        Called directly (``submit``, ``poll``, ``flush``, ``drain``), the
        batch is delivered before this returns.  Spans: ``vision.batch``
        over all of it; inside, in order, ``vision.assemble`` (stack,
        dtype, padding), ``vision.put``, ``vision.launch``,
        ``vision.sync``, ``vision.fetch`` and ``vision.deliver`` (row
        checks, results, done-callbacks).  Under a :class:`Completions`
        that defers this flush, ``vision.batch`` ends after the put (and
        the launch, where an earlier batch is in flight) and the rest
        runs on the completion thread.
        """
        comp = _deferring.get()
        if comp is None or not comp.reserve(self):
            with tracing.span("vision.batch"):
                self._complete(handles, *self._prepare(handles))
            return
        try:
            with tracing.span("vision.batch"):
                comp.hand(handles, *self._prepare(handles))
        except BaseException:
            comp.release()
            raise

    # -- request API ---------------------------------------------------------
    def submit(self, image: np.ndarray,
               deadline_ms: Optional[float] = None,
               poll: bool = True) -> Handle:
        """Queue one (H, W, 3) image; returns a handle whose ``result()``
        (this image's (n_classes,) logits) is delivered at flush — when the
        batch fills, the deadline fires, or ``flush()`` drains.

        ``deadline_ms``: optional per-request deadline — a queued request
        that is not executed within that many ms ends ``TIMED_OUT``.
        ``poll=False``: only enqueue; a batch this submit fills runs at
        the caller's next :meth:`poll` (the serving daemon polls outside
        its own lock).

        Raises ``ValueError`` on malformed payloads, validated UP FRONT so
        bad inputs fail here with a clear message, not as a poisoned batch
        later: wrong shape, non-numeric dtypes, or NaN/Inf pixels (which
        would corrupt the whole executed batch's numerics, not just this
        row's).  Raises ``QueueFullError`` when a bounded queue rejects
        the submit (see ``OverloadPolicy``).
        """
        with tracing.span("vision.validate"):
            img = np.asarray(image)
            if img.shape != (self.cfg.img_res, self.cfg.img_res, 3):
                raise ValueError(
                    f"expected ({self.cfg.img_res}, {self.cfg.img_res}, 3), "
                    f"got {img.shape}")
            if not np.issubdtype(img.dtype, np.number) \
                    or np.issubdtype(img.dtype, np.complexfloating):
                raise ValueError(
                    "image dtype must be real-numeric pixels, got "
                    f"{img.dtype}")
            if np.issubdtype(img.dtype, np.floating) \
                    and not np.all(np.isfinite(img)):
                raise ValueError(
                    "image holds NaN/Inf pixels; refusing to enqueue a "
                    "payload that would poison its whole executed batch")
        return self.scheduler.submit(img, deadline_ms=deadline_ms,
                                     poll=poll)

    def poll(self) -> int:
        """Execute whatever the flush policy says is due (a full batch, or
        pending requests older than ``max_delay_ms``).  Returns the number
        of requests RESOLVED — delivered or failed: executor exceptions
        fail only their batch's handles (each handle's ``result()``
        re-raises), never this call, so serving loops keep polling.
        (Under a deferring :class:`Completions`, the requests launched:
        their completion thread resolves them.)
        ``scheduler.next_deadline()`` says how long they may sleep first."""
        self.heartbeat = time.monotonic()
        return self.scheduler.poll()

    def flush(self) -> Optional[np.ndarray]:
        """Drain ALL pending images regardless of policy; returns the
        delivered (n, n_classes) logits in submit order (None if idle).

        Never raises on request failures: a failed batch or a non-finite
        row fails its own handles (absent from the returned stack; their
        ``result()`` re-raises the recorded exception) and the drain
        continues through the rest of the queue."""
        flushed = self.scheduler.drain()
        ok = [h.result() for h in flushed if h.state == DONE]
        if not ok:
            return None
        return np.stack(ok)

    def classify(self, images) -> np.ndarray:
        """(N, H, W, 3) images -> (N, n_classes) logits, any N >= 1 — the
        direct batch path, bypassing the queue (offline evaluation)."""
        images = np.asarray(images, np.float32)
        n = images.shape[0]
        if n == 0:
            return np.zeros((0, self.cfg.n_classes), np.float32)
        outs = []
        for start in range(0, n, self.B):
            chunk = images[start:start + self.B]
            outs.append(np.asarray(
                self._run_batch(chunk, self.bucket(chunk.shape[0]))))
            # keep sum(flush_reasons) == batches across mixed direct/queued
            # use (queued flushes record their reason in Scheduler.pop)
            self.stats.record_flush("direct")
        return np.concatenate(outs)


class _Step:
    """One padded batch put on the device (``VisionEngine._run_batch``).

    :meth:`launch` calls the jitted forward under the engine's fallback
    guard and returns without waiting on the device.  ``np.asarray``
    launches it where nothing did yet, waits on it (the guard's finite
    check, with its XLA retry), records the batch and fetches the n real
    rows, cut on the host: no slice of a varying n is ever compiled.
    """

    __slots__ = ("engine", "x", "n", "bucket", "_launched", "_rows")

    def __init__(self, engine: VisionEngine, x, n: int, bucket: int):
        self.engine = engine
        self.x = x
        self.n = n
        self.bucket = bucket
        self._launched = None   # the guard's (out, flags)
        self._rows: Optional[np.ndarray] = None

    def launch(self) -> None:
        if self._launched is None:
            eng = self.engine
            with eng._dispatch_scope():
                # span vision.launch
                self._launched = eng.fallback_guard.launch(
                    eng._fwd, eng.params, self.x)

    def __array__(self, dtype=None, copy=None) -> np.ndarray:
        if self._rows is None:
            self.launch()
            eng = self.engine
            with eng._dispatch_scope():
                # span vision.sync, and vision.launch for a retry
                logits = eng.fallback_guard.finish(
                    *self._launched, eng._fwd, eng.params, self.x)
            eng.stats.record_batch(items=self.n, padded=self.bucket - self.n,
                                   capacity=eng.B, bucket=self.bucket)
            with tracing.span("vision.fetch"):
                self._rows = np.asarray(logits)[:self.n]
            self.x = self._launched = None
        rows = self._rows if dtype is None else self._rows.astype(dtype)
        return rows.copy() if copy else rows


# the Completions whose deferring() block this thread is in
_deferring: contextvars.ContextVar[Optional["Completions"]] = \
    contextvars.ContextVar("vision_deferring", default=None)


class Completions:
    """The completion thread (``repro-complete``) that a
    :class:`~repro.serving.daemon.ServingDaemon` owns for its vision
    engine, so that the host prepares the next batch while the device
    runs the last.

    A flush made inside :meth:`deferring` runs only the first half of its
    batch on the flushing thread (the ``vision`` fault site, the stack
    and the put) and hands the batch here.  This thread completes batches
    in the order they were handed, each under the span
    ``vision.complete``: it waits on the oldest (the guard's finite
    check, with its XLA retry), fetches it and delivers it.  Where an
    earlier batch is still in flight, the flushing thread launches the
    forward itself, so that the step queues on the device behind the
    running one (``ServeStats.overlapped_batches`` counts these);
    otherwise this thread, which would only wait for it, launches it.
    At most ``DEPTH`` batches are in flight: a flush that would make a
    third waits until the oldest is completed.  An exception in the
    second half fails that batch's handles alone.
    """

    DEPTH = 2   # one batch running on the device, one queued behind it

    def __init__(self, engine: VisionEngine):
        self.engine = engine
        self.max_depth = 0   # the most batches that were in flight at once
        self.crashed: Optional[BaseException] = None
        self._cond = threading.Condition()
        self._queue: collections.deque = collections.deque()
        self._depth = 0      # batches reserved and not yet completed
        self._open = True
        self._thread = threading.Thread(target=self._run,
                                        name="repro-complete", daemon=True)

    def start(self) -> "Completions":
        self._thread.start()
        return self

    def close(self, join: bool = True) -> None:
        """Take no more batches (later flushes complete on their own
        thread); the thread completes every batch it holds, then ends.
        ``join``: wait for that."""
        with self._cond:
            self._open = False
            self._cond.notify_all()
        if join and self._thread.is_alive() \
                and self._thread is not threading.current_thread():
            self._thread.join()

    @contextlib.contextmanager
    def deferring(self):
        """Flushes of this engine made on this thread inside the block
        hand their second half to the completion thread."""
        token = _deferring.set(self)
        try:
            yield
        finally:
            _deferring.reset(token)

    def reserve(self, engine: VisionEngine) -> bool:
        """Room for one more of ``engine``'s batches in flight, waiting
        for it; False where the batch completes on the caller's thread:
        another engine's, the thread closed, or the caller is the
        completion thread itself (a done-callback that submits)."""
        if engine is not self.engine \
                or threading.current_thread() is self._thread:
            return False
        with self._cond:
            self._cond.wait_for(
                lambda: self._depth < self.DEPTH or not self._open)
            if not self._open:
                return False
            self._depth += 1
            self.max_depth = max(self.max_depth, self._depth)
            return True

    def release(self) -> None:
        """Give back a reservation whose batch failed before :meth:`hand`."""
        with self._cond:
            self._depth -= 1
            self._cond.notify_all()

    def hand(self, handles: List[Handle], step, act) -> None:
        """Queue a reserved batch for completion; launch it first where an
        earlier batch is still in flight."""
        with self._cond:
            behind = bool(self._queue)
        # (a fault wrapped around _run_batch may hand back finished rows)
        if behind and isinstance(step, _Step):
            step.launch()
            self.engine.stats.record_overlap()
        with self._cond:
            self._queue.append((handles, step, act))
            self._cond.notify_all()

    def _run(self) -> None:
        try:
            while True:
                with self._cond:
                    self._cond.wait_for(
                        lambda: self._queue
                        or (not self._open and not self._depth))
                    if not self._queue:
                        return
                    handles, step, act = self._queue[0]
                try:
                    with tracing.span("vision.complete"):
                        self.engine._complete(handles, step, act)
                except Exception as e:  # noqa: BLE001 — fails its batch
                    for h in handles:
                        h.set_exception(e)
                with self._cond:
                    self._queue.popleft()
                    self._depth -= 1
                    self._cond.notify_all()
        except BaseException as e:  # noqa: BLE001 — the daemon re-raises
            self.crashed = e
            self.close(join=False)
