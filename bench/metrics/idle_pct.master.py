"""Share of the traced window in which no operation ran on the device, in percent:
1 - (union of the device ops' intervals) / window, averaged over the chips."""


def read(ctx):
    s = ctx.summary
    return 100.0 * (1.0 - s.busy_s / s.window_s)
