"""recipe.quantize on the host clock (calibration, scheme choice,
weight quantization), s."""


def read(run):
    return run["quantize_s"]
