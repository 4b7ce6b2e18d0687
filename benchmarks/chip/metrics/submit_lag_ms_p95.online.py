"""95th percentile of due time -> ServingDaemon.submit returned, ms.  A
submit that fills a batch runs the forward on the caller's thread."""


def read(run):
    return run["lib"].percentile(run["submit_lag_ms"], 95)
