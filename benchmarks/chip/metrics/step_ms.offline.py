"""Mean device time of one executed forward program in the traced
window, ms."""


def read(run):
    return run["lib"].step_ms(run)
