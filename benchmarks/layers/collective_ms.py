"""mesh: profiler trace, durations of the collective ops (all-reduce,
all-gather, psum ...) on device 0 inside the window, mean per round."""


def read(ctx):
    if ctx.trace is None or not ctx.rounds:
        return None
    seconds = ctx.trace.collective_s
    return seconds * 1e3 / len(ctx.rounds) if seconds > 0 else None
