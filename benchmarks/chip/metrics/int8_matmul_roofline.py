"""int8_matmul's least time over its device time in the traced forwards,
%."""


def read(run):
    return run["lib"].roofline(run, "int8_matmul")
