"""colocation kernels: profiler trace, union of the device's op intervals
inside the benchmark's ``colo_tick`` spans of the window, fullest device,
mean per tick."""

from benchmarks import trace_reduce


def busy_and_ticks(ctx):
    """(device seconds inside the window's ``colo_tick`` spans, how many
    spans); (0.0, 0) with no trace or no such span."""
    if ctx.trace is None:
        return 0.0, 0
    spans = trace_reduce.clip(
        [(s, e) for name, s, e in ctx.trace.host_spans
         if name == "colo_tick"], [ctx.trace.window])
    return (trace_reduce.busy_s(ctx.trace.ops[ctx.trace.busiest], spans),
            len(spans))


def read(ctx):
    busy, ticks = busy_and_ticks(ctx)
    return busy * 1e3 / ticks if ticks and busy > 0 else None
