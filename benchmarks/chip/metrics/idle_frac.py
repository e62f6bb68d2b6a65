"""Share of the traced window in which no operation runs on the device,
per chip, then the mean."""


def read(ctx):
    red = ctx.red
    return 100.0 * (1.0 - red.busy_s / red.window_s)
