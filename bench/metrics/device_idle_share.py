"""Share of the traced window in which no leaf op ran on a chip, the mean
over the chips the cell uses, in percent."""


def read(ctx):
    if ctx.trace is None or ctx.trace.n_devices == 0 or ctx.window_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s() / ctx.window_s)
