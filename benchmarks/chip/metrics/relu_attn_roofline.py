"""relu_attn's least time over its device time in the traced forwards,
%."""


def read(run):
    return run["lib"].roofline(run, "relu_attn")
