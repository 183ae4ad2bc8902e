"""topology kernels: profiler trace, union of the device's op intervals
inside the program's ``topo.plan`` spans of the window, fullest device, mean
per span (one span a round: that round's gang plans).

The spans are the program's own timeline records (host ``perf_counter``),
laid onto the trace's clock by the benchmark's ``window`` span, which both
clocks hold.  A program that keeps no ``topo.plan`` record gives None."""

from benchmarks import trace_reduce


def plan_spans(ctx) -> list[tuple[float, float, float]]:
    """(start, end, gangs) of each ``topo.plan`` record inside the window,
    on the trace's clock; [] with no trace or no such record."""
    if ctx.trace is None:
        return []
    docs = getattr(ctx, "timeline_docs", None)
    if docs is None:
        from koordinator_tpu import timeline

        docs = timeline.RECORDER.cycles(64)
    window = ctx.spans.named("window")
    if not window:
        return []
    offset = ctx.trace.window[0] - window[0][1]
    out = []
    for doc in docs:
        for seg in doc.get("segments", ()):
            if seg.get("name") != "topo.plan" or "n" not in seg:
                continue
            start = doc["start"] + seg["start"]
            end = doc["start"] + seg["end"]
            lo, hi = max(start, ctx.t_open), min(end, ctx.t_close)
            if hi <= lo:
                continue
            share = (hi - lo) / (end - start)
            out.append((lo + offset, hi + offset, seg["n"] * share))
    return out


def busy_and_spans(ctx) -> tuple[float, list]:
    """(device seconds inside the window's ``topo.plan`` spans, the spans)."""
    spans = plan_spans(ctx)
    if not spans:
        return 0.0, []
    busy = trace_reduce.busy_s(ctx.trace.ops[ctx.trace.busiest],
                               [(s, e) for s, e, _ in spans])
    return busy, spans


def read(ctx):
    busy, spans = busy_and_spans(ctx)
    return busy * 1e3 / len(spans) if spans and busy > 0 else None
