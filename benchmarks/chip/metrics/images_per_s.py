"""Images whose request ended DONE inside the window, per second of
the window."""


def read(run):
    return run["done_in_window"] / run["window_s"]
