"""Reduce a profiler trace to the numbers the per-layer metrics read.

``load(path, spans)`` reads an ``.xplane.pb`` with
``jax.profiler.ProfileData`` and keeps three kinds of events, each as
``(name, start_ns, end_ns)`` from the trace's start:

* device ops: the ``XLA Ops`` line of every ``/device:`` plane;
* device programs: the ``XLA Modules`` line of the same planes, one event
  per executed jitted program;
* host spans whose name starts with ``SPAN_PREFIX``: events on any
  ``/host:`` plane (``TraceAnnotation`` spans, where the host tracer was
  on) and the benchmark's own ``spans``, taken on ``time.time_ns`` and
  placed by the trace's ``profile_start_time``.

``reduce(events)`` clips them to the window (the host span named
``SPAN_PREFIX + "window"``) and returns per-op device time, the busy union
of device ops, the idle gaps between them with the host span that overlaps
each gap most, per-program durations, and the device time of each op
kind inside the executions of the step program (the one with the most
device time).  With several devices, busy time is averaged over them.
"""
from __future__ import annotations

import bisect
import collections
import re
from typing import Dict, List, Tuple

SPAN_PREFIX = "bench:"
WINDOW = SPAN_PREFIX + "window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ENV_PLANE = "Task Environment"
START_STAT = "profile_start_time"
_SUFFIX = re.compile(r"(\.\d+)+$")

Event = Tuple[str, float, float]


def base_name(op: str) -> str:
    """``int8_matmul.3`` -> ``int8_matmul``: an HLO instruction's name
    without the numbers XLA appends to make it unique."""
    return _SUFFIX.sub("", op)


def op_name(event: str) -> str:
    """The HLO instruction's name: a TPU trace names each op by its whole
    instruction text, ``%int8_matmul.3 = f32[...] custom-call(...)``."""
    if event.startswith("%"):
        return event[1:].split(" ", 1)[0]
    return event


def load(path: str, spans: List[Event] = ()) -> dict:
    """{"ops": {device: [Event]}, "modules": {device: [Event]},
    "host": [Event]} from one ``.xplane.pb`` file, with ``spans`` (on
    ``time.time_ns``) among the host events."""
    from jax.profiler import ProfileData

    out = {"ops": {}, "modules": {}, "host": []}
    data = ProfileData.from_file(path)
    if spans:
        env = data.find_plane_with_name(ENV_PLANE)
        start = dict(env.stats).get(START_STAT) if env else None
        if start is None:
            raise ValueError(f"the trace has no {START_STAT!r} to place "
                             "the benchmark's spans by")
        out["host"] = [(n, s - start, e - start) for n, s, e in spans]
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key:
                    out[key][plane.name] = [
                        (op_name(e.name), e.start_ns,
                         e.start_ns + e.duration_ns) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"] += [
                    (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events if e.name.startswith(SPAN_PREFIX)]
    return out


def _clip(events: List[Event], lo: float, hi: float) -> List[Event]:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def _union(events: List[Event]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for _, s, e in sorted(events, key=lambda ev: ev[1]):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _overlap(a0, a1, b0, b1) -> float:
    return max(0.0, min(a1, b1) - max(a0, b0))


def _label(host: List[Event], g0: float, g1: float) -> str:
    """The host span that overlaps the gap (g0, g1) most."""
    best = max(host, key=lambda h: _overlap(g0, g1, h[1], h[2]), default=None)
    if best is None or _overlap(g0, g1, best[1], best[2]) <= 0:
        return "(no span)"
    return best[0][len(SPAN_PREFIX):]


def reduce(events: dict, top_gaps: int = 10) -> dict:
    """The window's device time, busy share, idle gaps and programs.

    Returns ``window_s``, ``busy_s`` (mean over devices), ``op_s`` (summed
    device seconds by op name), ``modules`` ({program name: [seconds of
    each execution]}), ``gaps``: [(seconds, host span name)] for every
    idle interval inside the window, longest first (only the ``top_gaps``
    longest are labelled; the rest carry None), ``step`` (the program with
    the most device time: the served forward), ``step_times`` (seconds of
    each of its executions) and ``kernel_s`` (device seconds by op base
    name inside those executions).  Raises ``ValueError``
    when the trace holds no window span or no device op in it.
    """
    windows = [(s, e) for n, s, e in events["host"] if n == WINDOW]
    if not windows:
        raise ValueError(f"no {WINDOW!r} span in the trace")
    lo, hi = windows[0]
    host = [ev for ev in _clip(events["host"], lo, hi) if ev[0] != WINDOW]
    op_s: Dict[str, float] = collections.defaultdict(float)
    modules: Dict[str, List[float]] = collections.defaultdict(list)
    busy, gaps = [], []
    for dev, ops in events["ops"].items():
        ops = _clip(ops, lo, hi)
        if not ops:
            continue
        for name, s, e in ops:
            op_s[name] += (e - s) * 1e-9
        spans = _union(ops)
        busy.append(sum(e - s for s, e in spans) * 1e-9)
        edges = [lo] + [t for span in spans for t in span] + [hi]
        gaps += [(g0, g1) for g0, g1 in zip(edges[::2], edges[1::2])
                 if g1 > g0]
    if not busy:
        raise ValueError("no device op inside the traced window")
    # programs: only executions that lie wholly inside the window
    runs = [(name, s, e) for mods in events["modules"].values()
            for name, s, e in mods if s >= lo and e <= hi]
    for name, s, e in runs:
        modules[name].append((e - s) * 1e-9)
    step = max(modules, key=lambda n: sum(modules[n]), default=None)
    # device time by op base name inside the executions of that program
    kernel_s: Dict[str, float] = collections.defaultdict(float)
    spans = sorted((s, e) for n, s, e in runs if n == step)
    starts = [a for a, _ in spans]
    for ops in events["ops"].values():
        for name, s, e in ops:
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and e <= spans[i][1]:
                kernel_s[base_name(name)] += (e - s) * 1e-9
    gaps.sort(key=lambda g: g[0] - g[1])
    gaps = [((g1 - g0) * 1e-9, _label(host, g0, g1) if k < top_gaps
             else None) for k, (g0, g1) in enumerate(gaps)]
    return {"window_s": (hi - lo) * 1e-9, "busy_s": sum(busy) / len(busy),
            "op_s": dict(op_s), "modules": dict(modules), "gaps": gaps,
            "step": step, "step_times": modules.get(step, []),
            "kernel_s": dict(kernel_s)}


def breakdown(reduced: dict, top: int = 10) -> dict:
    """The contract's ``breakdown``: the device ops that took the most time
    and the longest idle gaps, each labelled by the host span in it."""
    ops = sorted(reduced["op_s"].items(), key=lambda kv: -kv[1])[:top]
    gaps = reduced["gaps"][:top]
    return {"device_ops": [[n, t] for n, t in ops],
            "idle_gaps": [[label, t] for t, label in gaps]}
