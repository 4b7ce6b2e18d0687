"""The general traffic generator: one window of load from a mix's file.

A mix is a JSON file under ``traffic/`` whose ``kind`` picks the loop:

* ``closed``: ``outstanding`` clients, each sending its next image as
  soon as its last one is answered (bulk classification);
* ``poisson``: open-loop arrivals at ``rate_per_s`` with exponential gaps
  in an order drawn from the seed (independent interactive users).  Each request is
  timed from the moment it was due, so a stall also delays the requests
  behind it.

Both submit through ``ServingDaemon.submit(image, slo=...)`` under the
mix's SLO class and cycle through a pool of images in an order drawn from
the seed; every seed sends the same number of images per request and the
same kind of arrivals, in another order.
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import Callable, List, Optional

import numpy as np

clock = time.perf_counter
ARRIVAL_DRAW = 20241011     # the one draw of gaps every seed reorders


class Request:
    """One submitted image: which pool image, when it was due, when its
    submit returned, and when and how it ended."""

    __slots__ = ("image", "due", "submitted", "done", "handle")

    def __init__(self, image: int, due: float):
        self.image = image
        self.due = due
        self.submitted: Optional[float] = None
        self.done: Optional[float] = None
        self.handle = None


def arrivals(rate_per_s: float, seconds: float, rng) -> np.ndarray:
    """Poisson arrival offsets in [0, seconds): ``rate_per_s * seconds``
    arrivals, whose gaps are one fixed draw of exponential gaps scaled to
    the window (a Poisson process given its count), in an order drawn from
    ``rng``.  Every seed sends the same requests at the same set of gaps."""
    n = max(1, round(rate_per_s * seconds))
    gaps = np.random.default_rng(ARRIVAL_DRAW).exponential(1.0, n + 1)
    gaps = rng.permutation(gaps)
    return np.cumsum(gaps)[:n] * (seconds / gaps.sum())


class _Completions:
    """Counts finished requests for the closed loop (callbacks may run on
    the daemon's thread or inline on the submitting one)."""

    def __init__(self):
        self.cond = threading.Condition()
        self.finished = 0

    def mark(self, req: Request):
        def cb(_handle):
            req.done = clock()
            with self.cond:
                self.finished += 1
                self.cond.notify_all()
        return cb


def run(mix: dict, submit: Callable, pool: np.ndarray, rng, seconds: float,
        span=None) -> dict:
    """Drive one window; returns {"t0", "t1", "requests": [Request]}.

    ``submit(image_array) -> Handle``; ``span(name)`` gives a context
    manager around the generator's own phases (the benchmark's spans
    in traced runs).  After the window closes no request is sent, and every
    request sent is waited for by the caller.
    """
    span = span or (lambda name: contextlib.nullcontext())
    order = rng.permutation(len(pool))
    kind = mix["kind"]
    if kind == "closed":
        return _closed(mix, submit, pool, order, seconds, span)
    if kind == "poisson":
        due = arrivals(float(mix["rate_per_s"]), seconds, rng)
        return _poisson(due, submit, pool, order, seconds, span)
    raise ValueError(f"unknown traffic kind {kind!r}")


def _send(req: Request, submit, pool, span, on_done=None):
    """Submit ``req``; a submit that raises leaves it without a handle,
    which the caller counts as failed."""
    if on_done is None:
        def on_done(_h, r=req):
            r.done = clock()
    try:
        with span("submit"):
            req.handle = submit(pool[req.image])
    except Exception:  # noqa: BLE001 — a refused request is a failure
        req.submitted = clock()
        on_done(None)
        return
    req.submitted = clock()
    req.handle.add_done_callback(on_done)


def _closed(mix, submit, pool, order, seconds, span) -> dict:
    outstanding = int(mix["outstanding"])
    done = _Completions()
    reqs: List[Request] = []
    t0 = clock()
    t1 = t0 + seconds
    while True:
        now = clock()
        if now >= t1:
            break
        while len(reqs) - done.finished < outstanding and clock() < t1:
            req = Request(int(order[len(reqs) % len(order)]), clock())
            reqs.append(req)
            _send(req, submit, pool, span, done.mark(req))
        with span("wait"), done.cond:
            done.cond.wait_for(
                lambda: len(reqs) - done.finished < outstanding
                or clock() >= t1, timeout=max(0.0, t1 - clock()))
    # top the queue up to a whole batch, so that what the window left
    # queued runs at the one warmed shape; these are due after the window
    # and count in none of its numbers
    while len(reqs) % int(mix["max_batch"]):
        req = Request(int(order[len(reqs) % len(order)]), clock())
        reqs.append(req)
        _send(req, submit, pool, span, done.mark(req))
    return {"t0": t0, "t1": t1, "requests": reqs}


def _poisson(due, submit, pool, order, seconds, span) -> dict:
    reqs: List[Request] = []
    t0 = clock()
    for k, offset in enumerate(due):
        at = t0 + float(offset)
        wait = at - clock()
        if wait > 0:
            with span("sleep"):
                time.sleep(wait)
        req = Request(int(order[k % len(order)]), at)
        reqs.append(req)
        _send(req, submit, pool, span)
    t1 = t0 + seconds
    if clock() < t1:
        with span("sleep"):
            time.sleep(t1 - clock())
    return {"t0": t0, "t1": t1, "requests": reqs}
