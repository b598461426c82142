"""Share of the traced window in which no operation ran on the device:
1 - (union of device operation intervals / window), averaged over the
chips used. Moves ``updates_per_s``."""


def read(ctx):
    s = ctx.summary
    return 100.0 * (1.0 - s.busy_s / s.window_s)
