"""95th percentile of the scheduler's own queue wait (ServeStats.queue_ms:
submit -> the flush that started the request), ms."""


def read(run):
    return run["lib"].percentile(run["queue_ms"], 95)
