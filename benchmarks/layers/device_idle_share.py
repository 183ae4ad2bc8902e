"""device: 1 - union of device-op intervals / traced window, on the fullest
device."""


def read(ctx):
    if ctx.trace is None or ctx.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace.busiest_s / ctx.trace.window_s)
