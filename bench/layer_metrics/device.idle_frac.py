"""Device: the share of the traced window in which no op ran, mean over
the cell's chips."""


def read(ctx):
    trace = ctx["trace"]
    return 1.0 - trace["busy_s"] / trace["window_s"]
