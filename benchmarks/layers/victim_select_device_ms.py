"""descheduler kernels: profiler trace, union of the device's op intervals
inside the benchmark's ``desched_balance`` spans of the window, fullest
device, mean per descheduling round."""

from benchmarks import trace_reduce


def busy_and_rounds(ctx):
    """(device seconds inside the window's ``desched_balance`` spans,
    how many spans); (0.0, 0) with no trace or no such span."""
    if ctx.trace is None:
        return 0.0, 0
    spans = trace_reduce.clip(
        [(s, e) for name, s, e in ctx.trace.host_spans
         if name == "desched_balance"], [ctx.trace.window])
    return (trace_reduce.busy_s(ctx.trace.ops[ctx.trace.busiest], spans),
            len(spans))


def read(ctx):
    busy, rounds = busy_and_rounds(ctx)
    return busy * 1e3 / rounds if rounds and busy > 0 else None
