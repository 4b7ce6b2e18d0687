"""Host spans of the serving path, on the clock of the profiler's trace.

``with span("vision.put"): ...`` marks one phase of the program.  Spans
cost nothing unless a :func:`recording` is open: ``span`` then returns
one shared no-op object, after one read of a module global.  Inside
``with recording() as rec:`` every span that starts appends
``(name, start_ns, end_ns, thread_name)`` to ``rec.events`` when it ends,
on ``time.time_ns``; at most ``LIMIT`` events are kept and
``rec.dropped`` counts the rest.  One recording is open at a time, for the whole process.

To see what the device waited on, open a recording around a
``jax.profiler`` trace and place the spans on the trace's clock: an
``.xplane.pb`` holds the trace's start on ``time.time_ns`` as the stat
``profile_start_time`` of its ``Task Environment`` plane, so a span's
offset into the trace is ``start_ns - profile_start_time``::

    with tracing.recording() as rec:
        jax.profiler.start_trace(logdir)
        ...                                  # serve
        jax.profiler.stop_trace()
    # rec.events, less profile_start_time, lie on the device ops' clock
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import Iterator, List, Optional, Tuple

LIMIT = 1 << 20          # events one recording keeps

Event = Tuple[str, int, int, str]


class Recording:
    """The events of one open :func:`recording` (see the module doc)."""

    def __init__(self):
        self.limit = LIMIT
        self.events: List[Event] = []
        self.dropped = 0
        self.open = True
        self._lock = threading.Lock()

    def add(self, name: str, start_ns: int, end_ns: int) -> None:
        event = (name, start_ns, end_ns, threading.current_thread().name)
        with self._lock:
            if not self.open:
                return
            if len(self.events) < self.limit:
                self.events.append(event)
            else:
                self.dropped += 1


class _Off:
    """What ``span`` hands out while nothing records."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> bool:
        return False


OFF = _Off()
_recording: Optional[Recording] = None
_opening = threading.Lock()


class _Span:
    __slots__ = ("rec", "name", "start")

    def __init__(self, rec: Recording, name: str):
        self.rec = rec
        self.name = name

    def __enter__(self):
        self.start = time.time_ns()
        return self

    def __exit__(self, *exc) -> bool:
        self.rec.add(self.name, self.start, time.time_ns())
        return False


def span(name: str):
    """A context manager marking ``name`` over its block (see module)."""
    rec = _recording
    return OFF if rec is None else _Span(rec, name)


@contextlib.contextmanager
def recording() -> Iterator[Recording]:
    """Record every span that starts inside the block; yields the
    :class:`Recording`.  A span still open when the block ends is not
    kept.  Raises ``RuntimeError`` if a recording is already open."""
    global _recording
    with _opening:
        if _recording is not None:
            raise RuntimeError("a tracing recording is already open")
        rec = _recording = Recording()
    try:
        yield rec
    finally:
        _recording = None
        with rec._lock:
            rec.open = False
