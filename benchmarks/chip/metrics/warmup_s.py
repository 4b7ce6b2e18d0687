"""One forward at every bucket the mix uses, through the engine, on the
host clock, s."""


def read(run):
    return run["warmup_s"]
