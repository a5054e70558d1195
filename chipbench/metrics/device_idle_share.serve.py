"""Share of the traced window in which no operation ran on the chip."""


def read(ctx):
    return 100.0 * (1.0 - ctx.device["busy_s"] / ctx.device["window_s"])
