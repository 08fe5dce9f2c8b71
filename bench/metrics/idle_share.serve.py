"""Share of the traced window in which no op ran on the device (%), averaged
over the chips."""


def read(ctx):
    red = ctx.reduction
    return 100.0 * (1.0 - sum(red.busy_s) / len(red.busy_s) / red.window_s)
