"""Device: share of the traced window in which no operation ran, %."""


def read(ctx):
    red = ctx.reduction
    if red is None or not red.window_ns:
        return None
    return 100.0 * (1.0 - red.busy_ns / red.window_ns)
