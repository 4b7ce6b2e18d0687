"""Median latency over every request due in the window that was
answered, from its due time to its answer, ms."""


def read(run):
    return run["lib"].percentile(run["latency_ms"], 50)
