"""95th percentile latency over every request due in the window that was
answered, from its due time to its answer, ms.  Per layer: host stalls
move this tail too far from run to run for an end-to-end bound."""


def read(run):
    return run["lib"].percentile(run["latency_ms"], 95)
