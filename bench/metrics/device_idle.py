"""device_idle.<cells>: the share of the traced window in which no operation
ran on the chip, in percent."""


def read(ctx):
    if ctx.trace is None or not ctx.trace.ops:
        return None
    a, b = ctx.trace.window()
    return 100.0 * (1.0 - ctx.trace.busy.covered(a, b) / (b - a))
