"""Set-up: process start to the window's start (weights, quantize,
engine, warm-up of every bucket the mix uses, image pool, daemon), s."""


def read(run):
    return run["setup_s"]
