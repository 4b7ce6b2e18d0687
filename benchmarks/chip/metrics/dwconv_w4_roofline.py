"""dwconv_w4's least time over its device time in the traced forwards, %."""


def read(run):
    return run["lib"].roofline(run, "dwconv_w4")
