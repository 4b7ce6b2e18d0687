"""What the metric readers share.  Each reader gets the run record that
``harness.run_cell`` builds: host-clock times and per-request lists over
the whole window, and, in a traced run, ``trace`` (see ``trace.reduce``).
A reader that finds nothing to read returns None, never 0."""
from __future__ import annotations

import numpy as np


def percentile(values, q: float):
    """The ``q``-th percentile over every value (all requests)."""
    return float(np.percentile(values, q)) if len(values) else None


def step_s(run: dict):
    """Mean device seconds of one executed forward in the traced window."""
    t = run["trace"]
    if not t or not t["step_times"]:
        return None
    return float(np.mean(t["step_times"]))


def step_ms(run: dict):
    s = step_s(run)
    return None if s is None else s * 1e3


def mfu(run: dict):
    """The forward's model operations per step over its device time and
    the chip's int8 peak, in %."""
    s = step_s(run)
    if s is None:
        return None
    ops = run["ops_per_image"] * run["images_per_batch"]
    return ops / s / run["peaks"]["int8_ops_per_s"] * 100


def roofline(run: dict, kernel: str):
    """``kernel``'s least time (the larger of its operations over the peak
    and its minimal bytes over HBM bandwidth, from ``workcount``) over its
    device time in the traced forwards, in %."""
    t = run["trace"]
    calls = [c for c in run["launches"] if c["kind"] == kernel]
    spent = t["kernel_s"].get(kernel, 0.0) if t else 0.0
    if not calls or spent <= 0 or not t["step_times"]:
        return None
    wc = run["workcount"](kernel)
    peak, bw = run["peaks"][wc.PEAK], run["peaks"]["hbm_bytes_per_s"]
    least = 0.0
    for c in calls:
        ops, nbytes = wc.work(c, run["dtype_bytes"])
        least += max(ops / peak, nbytes / bw)
    return least * len(t["step_times"]) / spent * 100


def idle_share(run: dict):
    """1 - the busy union of device ops over the traced window, in %."""
    t = run["trace"]
    if not t or t["window_s"] <= 0:
        return None
    return (1.0 - t["busy_s"] / t["window_s"]) * 100
