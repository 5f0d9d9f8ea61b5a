"""Share of the traced window in which the chip ran no operation, in the
online cell (mean over the chips used)."""


def read(ctx):
    if ctx.red.window_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.red.busy_s / ctx.red.window_s)
