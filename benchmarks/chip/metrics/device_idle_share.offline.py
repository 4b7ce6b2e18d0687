"""Share of the traced window in which no op ran on the device, %."""


def read(run):
    return run["lib"].idle_share(run)
