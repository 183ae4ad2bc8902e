"""What the metric readers read: the run's books, spans, flight records and,
in a traced run, the reduced trace."""

from __future__ import annotations

import glob
import json
import os

from benchmarks import trace_reduce


class TraceView:
    """The window of a traced run, reduced once for all readers."""

    def __init__(self, trace: dict):
        spans = trace_reduce.bench_spans(trace)
        windows = [(s, e) for name, s, e in spans if name == "window"]
        if len(windows) != 1:
            raise RuntimeError(f"trace holds {len(windows)} window spans")
        self.window = windows[0]
        self.window_s = self.window[1] - self.window[0]
        self.host_spans = [sp for sp in spans if sp[0] != "window"]
        solves = [(s, e) for name, s, e in spans if name == "solve_request"]
        self.solve_windows = trace_reduce.clip(solves, [self.window])
        self.ops = trace_reduce.device_ops(trace)
        if not self.ops:
            raise RuntimeError("trace holds no TPU device plane")
        busy = {dev: trace_reduce.busy_s(ops, [self.window])
                for dev, ops in self.ops.items()}
        self.busiest = max(busy, key=busy.get)
        self.busiest_s = busy[self.busiest]
        self.mean_busy_s = sum(busy.values()) / len(busy)
        self.solve_busy_s = trace_reduce.busy_s(self.ops[self.busiest],
                                                self.solve_windows)
        self.collective_s = trace_reduce.collective_s(
            self.ops[min(self.ops)], [self.window])

    def breakdown(self, host_segments: list[tuple[str, float, float]]
                  ) -> dict:
        """Top device ops by time, and the longest idle gaps of the fullest
        device, each named by what the host was doing at its middle: the
        benchmark's own span, then the scheduler's innermost timeline
        segment (``host_segments``, already on the trace's clock)."""
        ops = self.ops[self.busiest]
        sums = trace_reduce.op_sums(ops, [self.window])
        top = sorted(sums.items(), key=lambda kv: -kv[1])[:10]

        def innermost(spans, at):
            covering = [(e - s, name) for name, s, e in spans if s <= at < e]
            return min(covering)[1] if covering else None

        longest = sorted(trace_reduce.idle_gaps(ops, self.window),
                         key=lambda g: g[0] - g[1])[:10]
        gaps = []
        for s, e in longest:
            mid = (s + e) / 2
            names = [innermost(self.host_spans, mid) or "between_spans",
                     innermost(host_segments, mid)]
            gaps.append([":".join(n for n in names if n), e - s])
        return {"device_ops": [[n, v] for n, v in top],
                "idle_gaps": gaps}


def load_trace(trace_dir: str, dump_to: str | None = None) -> TraceView:
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(found) != 1:
        raise RuntimeError(f"{len(found)} xplane files under {trace_dir}")
    trace = trace_reduce.load(found[0])
    if dump_to:
        with open(dump_to, "w") as f:
            json.dump(trace, f)
    return TraceView(trace)


class Context:
    def __init__(self, **fields):
        self.trace: TraceView | None = None
        self.__dict__.update(fields)

    def _window(self, name: str):
        return [r for r in self.spans.named(name) if r[1] >= self.t_open]

    def window_total_s(self, name: str) -> float:
        return sum(t1 - t0 for _, t0, t1, _ in self._window(name))

    def window_count(self, name: str, key: str) -> int:
        return sum(c.get(key, 0) for *_, c in self._window(name))
