"""Model operations of the forward's images over its device time and the
chip's int8 peak, %."""


def read(run):
    return run["lib"].mfu(run)
