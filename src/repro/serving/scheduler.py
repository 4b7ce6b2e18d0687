"""The shared async scheduler core behind both serving engines.

One queue discipline for both modalities: requests enter through
``submit()`` and get a :class:`Handle` back immediately (a future — the
result is delivered when the batch holding the request executes).  A batch
executes when the pluggable :class:`FlushPolicy` says so:

* **full**      — ``max_batch`` requests are waiting, or
* **deadline**  — the OLDEST waiting request's age exceeds
  ``max_delay_ms`` (the latency guarantee: no request waits longer than
  one deadline for admission, however quiet the traffic), or
* **drain**     — an explicit ``drain()``/``flush()`` call.

The clock is injectable (``clock=`` returns seconds, default
``time.monotonic``) so tests and ``benchmarks/serving_bench.py`` drive
deadline behavior with virtual time instead of sleeping.  All scheduler
arithmetic runs on a MONOTONIC GUARD over that clock (:meth:`now`): a
clock that stalls simply freezes ages, and one that steps backwards can
neither make an age negative nor un-fire a deadline that already passed.

Failure story (the fault-tolerance layer):

* Handles are a terminal-state machine — ``PENDING`` then exactly one of
  ``DONE`` / ``FAILED`` / ``CANCELLED`` / ``TIMED_OUT``.  Executor
  exceptions in :meth:`poll`/:meth:`drain` fail ONLY the handles of the
  batch that was executing (``set_exception``) and the loop keeps
  serving; they never propagate out of the scheduler.
* Admission control: an :class:`OverloadPolicy` bounds the queue —
  reject new submits with :class:`~repro.serving.errors.QueueFullError`,
  or shed the oldest waiting request to make room.
* Per-request deadlines (``submit(..., deadline_ms=)``) expire queued
  requests to ``TIMED_OUT`` (:meth:`expire`, folded into :meth:`due` /
  :meth:`poll`); engines expire their *in-flight* requests the same way.
* Every outcome lands in the shared
  :class:`~repro.serving.batching.ServeStats` counters, so
  ``submitted == completed + failed + cancelled + timed_out + shed``
  always reconciles.

Two usage modes share the same core:

* **executor mode** (VisionEngine): the scheduler owns execution — give it
  an ``executor(handles, reason)`` callable and call :meth:`poll`
  periodically; due batches run and deliver results into their handles.
  ``submit()`` polls opportunistically, so a full batch executes inline.
* **admission mode** (token Engine): the engine owns execution (slots,
  prefill grouping, the decode loop) and uses :meth:`due` / :meth:`peek` /
  :meth:`pop` to decide *when* and *which* waiting requests to admit —
  queue latency and flush accounting still land in the shared
  :class:`~repro.serving.batching.ServeStats`.
"""
from __future__ import annotations

import dataclasses
import itertools
import threading
import time
from typing import Callable, Iterator, List, Optional, Sequence

from .. import tracing
from .batching import ServeStats
from .errors import CancelledError, QueueFullError, RequestTimedOut

# flush reasons (ServeStats.flush_reasons keys)
FLUSH_FULL = "full"
FLUSH_DEADLINE = "deadline"
FLUSH_DRAIN = "drain"

# Handle states: PENDING, then exactly one terminal state
PENDING = "PENDING"
DONE = "DONE"
FAILED = "FAILED"
CANCELLED = "CANCELLED"
TIMED_OUT = "TIMED_OUT"

# terminal state -> ServeStats outcome counter it increments
_STATE_OUTCOME = {DONE: "completed", FAILED: "failed",
                  CANCELLED: "cancelled", TIMED_OUT: "timed_out"}


@dataclasses.dataclass(frozen=True)
class FlushPolicy:
    """When does a waiting batch execute?

    ``max_delay_ms=None`` disables the deadline (only full batches and
    explicit drains flush — the old explicit-flush batcher behavior);
    ``max_delay_ms=0.0`` flushes whenever anything is pending (the token
    engine's admit-on-free-slot behavior).

    Raises ``ValueError`` for a non-positive ``max_batch`` or a negative
    ``max_delay_ms``.
    """

    max_batch: int = 64
    max_delay_ms: Optional[float] = None

    def __post_init__(self):
        if self.max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {self.max_batch}")
        if self.max_delay_ms is not None and self.max_delay_ms < 0:
            raise ValueError(
                f"max_delay_ms must be >= 0 or None, got {self.max_delay_ms}")

    def admission_deadline(self, queue: Sequence["Handle"]) -> Optional[float]:
        """Absolute clock time at which the waiting queue becomes due for
        a deadline flush (None: no deadline applies).  The scheduler's
        :meth:`Scheduler.due` compares ``now >= admission_deadline()`` and
        :meth:`Scheduler.next_deadline` returns this same value, so a loop
        that slept exactly until the returned deadline IS due — one shared
        arithmetic, no float-ulp miss.  Subclasses override this to
        implement richer policies (per-SLO-class delays: see
        :class:`~repro.serving.slo.ClassFlushPolicy`)."""
        if not queue or self.max_delay_ms is None:
            return None
        return (min(h.submitted_at for h in queue)
                + self.max_delay_ms / 1000.0)


@dataclasses.dataclass(frozen=True)
class OverloadPolicy:
    """Admission control: what happens when the queue is full.

    ``max_queue=None`` (default) leaves the queue unbounded — exactly the
    pre-admission-control behavior.  With a bound, a submit that finds
    ``max_queue`` requests already waiting either raises
    :class:`~repro.serving.errors.QueueFullError` (``shed_oldest=False``,
    counted in ``ServeStats.rejected``) or sheds the OLDEST waiting
    request to make room (``shed_oldest=True``: the shed handle ends
    ``FAILED`` with a ``QueueFullError`` and counts in
    ``ServeStats.shed`` — freshest-traffic-wins load shedding).

    Raises ``ValueError`` for a non-positive ``max_queue``.
    """

    max_queue: Optional[int] = None
    shed_oldest: bool = False

    def __post_init__(self):
        if self.max_queue is not None and self.max_queue < 1:
            raise ValueError(
                f"max_queue must be >= 1 or None, got {self.max_queue}")


class Handle:
    """A submitted request: a future with a terminal-state machine.

    States: ``PENDING`` until the scheduler/engine delivers exactly one
    terminal transition — ``DONE`` (``set_result``), ``FAILED``
    (``set_exception``), ``CANCELLED`` (``cancel``), or ``TIMED_OUT``
    (deadline expiry).  Terminal states are sticky: late transitions (an
    executor delivering into a handle the caller already cancelled) are
    dropped, and every transition is counted once in the scheduler's
    ``ServeStats``.

    ``result()`` raises ``RuntimeError`` while the request is still
    PENDING (drive the scheduler — ``poll()`` until the deadline passes,
    or ``drain()`` — or pass ``timeout=`` to block on the real clock);
    for a failed/cancelled/timed-out request it re-raises the recorded
    exception.

    Thread-safety: all transitions and waits synchronize on one internal
    condition variable, so a daemon thread resolving the handle wakes a
    blocked ``result(timeout=)`` / ``tokens()`` caller immediately
    (event-based — no sleep-polling jitter).  Streaming: producers push
    incremental tokens with :meth:`push_token`; consumers iterate
    :meth:`tokens` (blocking) or register an ``on_token`` callback.
    ``add_done_callback`` fires once at the terminal transition (callbacks
    run outside the handle's lock, on the resolving thread; exceptions
    they raise are swallowed so they can never break engine containment).
    """

    __slots__ = ("uid", "payload", "submitted_at", "deadline", "state",
                 "priority", "_result", "_exception", "_stats", "_cond",
                 "_stream", "_on_token", "_callbacks")

    def __init__(self, uid: int, payload, submitted_at: float,
                 deadline: Optional[float] = None,
                 stats: Optional[ServeStats] = None,
                 priority: int = 0,
                 on_token: Optional[Callable[[int], None]] = None):
        self.uid = uid
        self.payload = payload
        self.submitted_at = submitted_at
        self.deadline = deadline  # absolute clock seconds, or None
        self.priority = priority  # higher admits first (SLO classes)
        self.state = PENDING
        self._result = None
        self._exception: Optional[BaseException] = None
        self._stats = stats
        self._cond = threading.Condition()
        self._stream: List[int] = []   # incrementally delivered tokens
        self._on_token = on_token
        self._callbacks: List[Callable[["Handle"], None]] = []

    # -- state machine -------------------------------------------------------
    def _finish(self, state: str, result=None,
                exc: Optional[BaseException] = None,
                count_as: Optional[str] = None) -> bool:
        """One-shot transition PENDING -> ``state``; False if already
        terminal (the transition is dropped, nothing is overwritten)."""
        with self._cond:
            if self.state != PENDING:
                return False
            self.state = state
            self._result = result
            self._exception = exc
            if self._stats is not None:
                self._stats.record_outcome(count_as or _STATE_OUTCOME[state])
            callbacks, self._callbacks = self._callbacks, []
            self._cond.notify_all()
        for cb in callbacks:  # outside the lock: a callback may inspect us
            try:
                cb(self)
            except Exception:  # noqa: BLE001 — callbacks must not break
                pass           # the resolving engine's containment
        return True

    def add_done_callback(self, fn: Callable[["Handle"], None]) -> None:
        """Run ``fn(handle)`` once the handle reaches ANY terminal state
        (immediately if it already has).  Runs on the resolving thread,
        outside the handle's lock; exceptions are swallowed."""
        with self._cond:
            if self.state == PENDING:
                self._callbacks.append(fn)
                return
        try:
            fn(self)
        except Exception:  # noqa: BLE001 — see add-time contract
            pass

    # -- streaming -----------------------------------------------------------
    def push_token(self, token: int) -> bool:
        """Deliver one incremental token (producer side: the engine's
        decode loop).  Dropped once the handle is terminal.  Wakes
        :meth:`tokens` iterators; invokes the ``on_token`` callback (set
        via ``Engine.submit(on_token=)``) outside the lock, on the
        producing thread — exceptions it raises are swallowed."""
        with self._cond:
            if self.state != PENDING:
                return False
            self._stream.append(int(token))
            cb = self._on_token
            self._cond.notify_all()
        if cb is not None:
            try:
                cb(int(token))
            except Exception:  # noqa: BLE001 — user callback cannot break
                pass           # the engine loop
        return True

    @property
    def streamed(self) -> int:
        """Tokens pushed so far (monotonic; final result may hold more —
        tokens decoded and completed in the same step arrive together)."""
        with self._cond:
            return len(self._stream)

    def tokens(self, timeout: Optional[float] = None) -> Iterator[int]:
        """Blocking iterator over streamed tokens, in decode order.

        Yields each token as the engine produces it (requires a streaming
        submit — ``Engine.submit(stream=True)`` or ``on_token=`` — and
        something concurrently driving the engine, e.g. the serving
        daemon).  Ends when the handle resolves: normally on ``DONE``
        (after draining every pushed token), re-raising the recorded
        exception on FAILED / CANCELLED / TIMED_OUT — tokens already
        yielded stand, the failure tells the consumer the stream is
        truncated.  ``timeout``: max seconds to wait for EACH next token
        (real clock); raises ``TimeoutError`` when it expires.
        """
        pos = 0
        while True:
            with self._cond:
                while pos >= len(self._stream) and self.state == PENDING:
                    if not self._cond.wait(timeout=timeout):
                        raise TimeoutError(
                            f"request {self.uid}: no token within "
                            f"{timeout}s (is anything driving the "
                            "engine?)")
                if pos < len(self._stream):
                    tok = self._stream[pos]
                    pos += 1
                else:  # terminal and fully drained
                    if self.state == DONE:
                        return
                    exc = self._exception
                    break
            yield tok
        raise exc

    def set_result(self, result) -> bool:
        """Deliver the result (-> DONE); dropped if already terminal."""
        return self._finish(DONE, result=result)

    def set_exception(self, exc: BaseException, state: str = FAILED,
                      count_as: Optional[str] = None) -> bool:
        """Fail the request (-> FAILED by default; pass ``state=TIMED_OUT``
        for deadline expiry).  ``count_as`` overrides which ServeStats
        outcome counter increments (load shedding counts as ``"shed"``
        while still ending FAILED).  Dropped if already terminal."""
        return self._finish(state, exc=exc, count_as=count_as)

    def cancel(self) -> bool:
        """Cancel a PENDING request (-> CANCELLED); returns False if it
        already reached a terminal state (too late to cancel).  A queued
        request never executes after this; an in-flight decode is swept at
        the engine's next step (its slot is freed)."""
        return self._finish(
            CANCELLED, exc=CancelledError(f"request {self.uid} cancelled"))

    # -- inspection ----------------------------------------------------------
    def done(self) -> bool:
        """True once the handle reached ANY terminal state."""
        return self.state != PENDING

    def cancelled(self) -> bool:
        return self.state == CANCELLED

    def exception(self) -> Optional[BaseException]:
        """The recorded failure (None while PENDING or when DONE)."""
        return self._exception

    def result(self, timeout: Optional[float] = None):
        """The delivered result.

        Raises ``RuntimeError`` while the request is still PENDING and no
        ``timeout`` is given (this scheduler is poll-driven: drive it, or
        use ``timeout=`` seconds to block on the REAL clock — that only
        makes sense when something else concurrently drives the engine,
        e.g. the serving daemon; raises ``TimeoutError`` if the wait
        expires).  For a FAILED / CANCELLED / TIMED_OUT request this
        re-raises the recorded exception.
        """
        if self.state == PENDING and timeout is not None:
            # event-based wait: _finish notify_all()s this condition, so
            # the waiter wakes the instant the resolving thread delivers —
            # no sleep-poll jitter added to completion latency
            with self._cond:
                self._cond.wait_for(lambda: self.state != PENDING,
                                    timeout=timeout)
            if self.state == PENDING:
                raise TimeoutError(
                    f"request {self.uid} still PENDING after waiting "
                    f"{timeout}s (is anything driving the engine?)")
        if self.state == PENDING:
            raise RuntimeError(
                f"request {self.uid} has no result yet: it is still queued "
                "or executing; poll() until its deadline passes, or drain()")
        if self.state == DONE:
            return self._result
        raise self._exception

    def __repr__(self):
        return f"Handle(uid={self.uid}, {self.state})"


class Scheduler:
    """Deadline-driven priority/FIFO request queue (see module docstring).

    Thread-safety: all queue state is guarded by one internal
    re-entrant lock, so foreign threads may ``submit()``/``cancel()``
    while a daemon thread drives ``due()``/``pop()``/``poll()`` — the
    reconciliation invariant holds exactly under concurrency (proven by
    ``tests/test_daemon.py``'s stress test).  The executor itself runs
    OUTSIDE the lock (a long batch never blocks admission); lock order
    is scheduler lock -> handle condition, never the reverse.

    Priorities: ``submit(..., priority=)`` admits higher classes first
    (FIFO within a class — everything at the default priority 0 is the
    old pure-FIFO behavior).  Queue order is maintained sorted by
    descending priority, submit order within a class.
    """

    def __init__(self, policy: FlushPolicy = FlushPolicy(),
                 executor: Optional[Callable] = None,
                 stats: Optional[ServeStats] = None,
                 clock: Callable[[], float] = time.monotonic,
                 overload: Optional[OverloadPolicy] = None,
                 faults=None):
        self.policy = policy
        self.executor = executor
        self.stats = stats if stats is not None else ServeStats()
        self.clock = clock
        self.overload = overload if overload is not None else OverloadPolicy()
        self.faults = faults  # serving.faults.FaultInjector (site "executor")
        self._q: List[Handle] = []
        self._uids = itertools.count()  # monotonic: uids never collide
        self._last_now = float("-inf")  # monotonic guard over the clock
        self._lock = threading.RLock()

    # -- clock ---------------------------------------------------------------
    def now(self, now: Optional[float] = None) -> float:
        """Monotonic-guarded clock read: the max ever observed, so ages
        never go negative and fired deadlines never un-fire when the
        underlying clock stalls or steps backwards."""
        with self._lock:
            t = self.clock() if now is None else now
            if t > self._last_now:
                self._last_now = t
            return self._last_now

    # -- queue state ---------------------------------------------------------
    @property
    def pending(self) -> int:
        with self._lock:
            return len(self._q)

    def pending_payloads(self) -> list:
        """Payloads still queued, admission order (diagnostics / engine
        compat)."""
        with self._lock:
            return [h.payload for h in self._q]

    def oldest_age_ms(self, now: Optional[float] = None) -> float:
        with self._lock:
            if not self._q:
                return 0.0
            oldest = min(h.submitted_at for h in self._q)
            return max(0.0, (self.now(now) - oldest) * 1000.0)

    def next_deadline(self) -> Optional[float]:
        """Absolute clock time of the next event — a waiting request
        becoming due for admission (the policy's
        :meth:`FlushPolicy.admission_deadline`), or the earliest
        per-request deadline expiring (None if neither applies) — serving
        loops sleep until this instead of busy-polling."""
        with self._lock:
            cands = []
            adm = self.policy.admission_deadline(self._q)
            if adm is not None:
                cands.append(adm)
            cands.extend(h.deadline for h in self._q
                         if h.deadline is not None)
            return min(cands) if cands else None

    def expire(self, now: Optional[float] = None) -> int:
        """Sweep the queue: drop cancelled handles and transition queued
        requests past their per-request deadline to TIMED_OUT (counted in
        ``ServeStats.timed_out``).  Returns the number expired.  Folded
        into :meth:`due`, so poll loops get it for free."""
        with self._lock:
            now = self.now(now)
            keep: List[Handle] = []
            expired: List[Handle] = []
            for h in self._q:
                if h.state != PENDING:
                    continue  # cancelled (or externally finished): drop
                if h.deadline is not None and now >= h.deadline:
                    expired.append(h)
                else:
                    keep.append(h)
            self._q = keep
        for h in expired:  # transitions outside: they run done-callbacks
            h.set_exception(
                RequestTimedOut(
                    f"request {h.uid} expired in queue: deadline passed "
                    f"{(now - h.deadline) * 1000.0:.1f}ms ago"),
                state=TIMED_OUT)
        return len(expired)

    def due(self, now: Optional[float] = None) -> Optional[str]:
        """The flush reason if the policy wants a batch executed now
        (cancelled/expired requests are swept first).  The deadline check
        compares against :meth:`FlushPolicy.admission_deadline` — the
        same arithmetic :meth:`next_deadline` returns — so a caller that
        slept exactly until next_deadline() IS due (an age-based >= check
        can miss it by one float ulp and spin)."""
        with self._lock:
            now = self.now(now)
            self.expire(now)
            if not self._q:
                return None
            if len(self._q) >= self.policy.max_batch:
                return FLUSH_FULL
            deadline = self.policy.admission_deadline(self._q)
            if deadline is not None and now >= deadline:
                return FLUSH_DEADLINE
            return None

    # -- request API ---------------------------------------------------------
    def _insert(self, h: Handle) -> None:
        """Insert maintaining (descending priority, FIFO within class):
        scan back over the strictly-lower-priority tail.  All-default
        priorities degenerate to append — the pure-FIFO fast path."""
        i = len(self._q)
        while i > 0 and self._q[i - 1].priority < h.priority:
            i -= 1
        self._q.insert(i, h)

    def submit(self, payload, deadline_ms: Optional[float] = None,
               priority: int = 0,
               on_token: Optional[Callable[[int], None]] = None,
               poll: bool = True) -> Handle:
        """Enqueue one request; returns its :class:`Handle` immediately.

        ``deadline_ms``: optional per-request deadline (relative to now);
        the request TIMES OUT — queued or in flight — once it passes.
        ``priority``: higher admits first (FIFO within equal priority);
        the default 0 preserves pure-FIFO behavior.
        ``on_token``: optional per-token streaming callback installed on
        the handle (invoked by the producer via ``Handle.push_token``).
        ``poll``: with an executor, execute what is due before returning
        (a now-full batch runs inline); ``poll=False`` leaves that to the
        caller's own :meth:`poll`.

        Raises :class:`~repro.serving.errors.QueueFullError` when an
        :class:`OverloadPolicy` bounds the queue, it is full, and the
        policy rejects rather than sheds (with ``shed_oldest=True`` the
        oldest waiting request of the LOWEST priority class is shed —
        failed with ``QueueFullError``, counted in ``ServeStats.shed`` —
        and this submit succeeds).
        Raises ``ValueError`` for a non-positive ``deadline_ms``.
        """
        if deadline_ms is not None and deadline_ms <= 0:
            raise ValueError(f"deadline_ms must be > 0, got {deadline_ms}")
        shed: List[Handle] = []
        # span sched.enqueue: the wait for the queue lock and the section
        # under it, not the shed transitions or the inline poll below
        with tracing.span("sched.enqueue"), self._lock:
            now = self.now()
            self.expire(now)
            cap = self.overload.max_queue
            if cap is not None:
                while len(self._q) - len(shed) >= cap:
                    if not self.overload.shed_oldest:
                        self.stats.record_outcome("rejected")
                        raise QueueFullError(
                            f"queue full: {len(self._q)} waiting >= "
                            f"max_queue={cap} (OverloadPolicy rejects; use "
                            "shed_oldest=True to shed instead)")
                    # victim: oldest of the lowest-priority class — the
                    # sorted invariant puts that class at the tail, its
                    # oldest first within the tail
                    minp = min(h.priority for h in self._q
                               if h not in shed)
                    victim = next(h for h in self._q
                                  if h.priority == minp and h not in shed)
                    shed.append(victim)
                taken = {id(h) for h in shed}
                self._q = [h for h in self._q if id(h) not in taken]
            h = Handle(uid=next(self._uids), payload=payload,
                       submitted_at=now,
                       deadline=(None if deadline_ms is None
                                 else now + deadline_ms / 1000.0),
                       stats=self.stats, priority=priority,
                       on_token=on_token)
            self._insert(h)
            self.stats.submitted += 1
        for old in shed:  # transitions outside the lock (done-callbacks)
            old.set_exception(
                QueueFullError(
                    f"request {old.uid} shed: queue hit max_queue="
                    f"{self.overload.max_queue} and OverloadPolicy sheds "
                    "oldest"),
                count_as="shed")
        if poll and self.executor is not None:
            self.poll(now)  # a now-full batch executes inline
        return h

    def requeue(self, handle: Handle) -> bool:
        """Re-insert a still-PENDING handle at the back of its priority
        class (preemption continuation: the engine evicted its decode
        slot and resubmits the remaining work).  Resets ``submitted_at``
        to now — queue latency then measures each admission wait, not the
        total — does NOT count a new submit (the reconciliation invariant
        stays ``submitted == sum(outcomes)``), and bypasses the overload
        bound (preemptions are engine-internal: their number is bounded
        by the slot count, not client traffic).  Returns False (no-op) if
        the handle is already terminal."""
        with self._lock:
            if handle.state != PENDING:
                return False
            handle.submitted_at = self.now()
            self._insert(handle)
            return True

    # -- admission mode (the engine owns execution) --------------------------
    def peek(self, n: int) -> List[Handle]:
        """Up to ``n`` next-admittable PENDING handles in admission order
        (priority, then FIFO), not removed (the token engine groups them
        by prompt length before committing to a prefill batch)."""
        with self._lock:
            return [h for h in self._q if h.state == PENDING][: max(0, n)]

    def pop(self, handles: Sequence[Handle], reason: str) -> List[Handle]:
        """Remove ``handles`` from the queue; stamps each one's queue
        latency and the batch's flush reason into the shared stats.
        Returns only the handles still PENDING (cancelled/expired ones
        are dropped, never executed)."""
        with self._lock:
            now = self.now()
            taken = {id(h) for h in handles}
            self._q = [h for h in self._q if id(h) not in taken]
            live = [h for h in handles if h.state == PENDING]
            for h in live:
                self.stats.record_latency((now - h.submitted_at) * 1000.0)
            if live:
                self.stats.record_flush(reason)
            return live

    # -- executor mode (the scheduler owns execution) ------------------------
    def _run_executor(self, handles: List[Handle], reason: str) -> None:
        """One executor call with per-batch failure containment: an
        exception (including an injected fault) fails ONLY this batch's
        handles; it never propagates, so the serving loop keeps running."""
        act = self.faults.on_call("executor") if self.faults else None
        try:
            if act is not None:
                act.fire()
            self.executor(handles, reason)
        except Exception as e:  # noqa: BLE001 — containment is the point
            for h in handles:
                h.set_exception(e)

    def poll(self, now: Optional[float] = None) -> int:
        """Execute every batch the policy says is due.  Returns the number
        of requests resolved (delivered OR failed — executor exceptions
        fail the batch's handles and the loop keeps serving).  No-op
        without an executor.  The executor runs OUTSIDE the queue lock:
        foreign threads keep submitting while a batch executes."""
        if self.executor is None:
            return 0
        delivered = 0
        while True:
            with self._lock:
                reason = self.due(now)
                if reason is None:
                    return delivered
                handles = self.pop(self._q[: self.policy.max_batch], reason)
            if not handles:
                continue  # batch was entirely cancelled/expired
            self._run_executor(handles, reason)
            delivered += len(handles)

    def drain(self) -> List[Handle]:
        """Flush EVERYTHING pending regardless of policy (shutdown, or the
        legacy explicit-flush API).  Returns the flushed handles in
        admission order (executor failures fail their batch's handles; the
        drain continues).  Raises ``RuntimeError`` without an executor —
        admission-mode callers pop() and execute themselves."""
        if self.executor is None:
            raise RuntimeError("drain() needs an executor; admission-mode "
                               "callers pop() and execute themselves")
        flushed: List[Handle] = []
        while True:
            with self._lock:
                if not self._q:
                    return flushed
                handles = self.pop(self._q[: self.policy.max_batch],
                                   FLUSH_DRAIN)
            if not handles:
                continue
            self._run_executor(handles, FLUSH_DRAIN)
            flushed.extend(handles)
