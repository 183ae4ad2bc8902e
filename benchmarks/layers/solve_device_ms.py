"""solve kernels: profiler trace, union of the device's op intervals inside
the window's solve requests, fullest device, mean per round."""


def read(ctx):
    if ctx.trace is None or not ctx.rounds:
        return None
    busy = ctx.trace.solve_busy_s
    return busy * 1e3 / len(ctx.rounds) if busy > 0 else None
