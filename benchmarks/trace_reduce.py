"""From a profiler trace to numbers: busy union, idle gaps, per-op sums.

A trace is held in a plain form, ``{"planes": [{"name", "lines": [{"name",
"events": [[name, start_ns, duration_ns], ...]}]}]}``, which ``load`` makes
from the ``.xplane.pb`` the JAX profiler writes and which
``tests/data/recorded_trace.json`` holds a small recorded piece of.  All
intervals below are (start, end) in seconds on the trace's clock.
"""

from __future__ import annotations

import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
BENCH_PREFIX = "bench:"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|all-to-all|reduce-scatter|collective-permute"
    r"|psum|all_reduce|all_gather", re.IGNORECASE)


def short_name(op: str) -> str:
    """``%while.12 = (s32[] ...) while(...)`` -> ``while.12``: the trace
    names a device op by its whole HLO line."""
    return op.split(" = ", 1)[0].lstrip("%")


def load(xplane_path: str) -> dict:
    """Of the device planes the ``XLA Ops`` line, and of the host planes
    only the benchmark's own annotations (the rest is large and read by
    nothing)."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(xplane_path).planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            if device:
                events = [[short_name(e.name), float(e.start_ns),
                           float(e.duration_ns)] for e in line.events]
            else:
                events = [[e.name, float(e.start_ns), float(e.duration_ns)]
                          for e in line.events
                          if e.name.startswith(BENCH_PREFIX)]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def device_ops(trace: dict) -> dict[str, list[tuple[str, float, float]]]:
    """{device plane: [(op name, start_s, end_s)]} from its XLA Ops line."""
    out = {}
    for plane in trace["planes"]:
        if not DEVICE_PLANE.match(plane["name"]):
            continue
        ops = [(name, start / 1e9, (start + dur) / 1e9)
               for line in plane["lines"] if line["name"] == OPS_LINE
               for name, start, dur in line["events"]]
        out[plane["name"]] = sorted(ops, key=lambda op: op[1])
    return out


def bench_spans(trace: dict) -> list[tuple[str, float, float]]:
    """The benchmark's annotations, (name without prefix, start_s, end_s)."""
    return sorted(
        ((name[len(BENCH_PREFIX):], start / 1e9, (start + dur) / 1e9)
         for plane in trace["planes"] for line in plane["lines"]
         for name, start, dur in line["events"]
         if name.startswith(BENCH_PREFIX)), key=lambda s: s[1])


def union(intervals) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for start, end in sorted(intervals):
        if end <= start:
            continue
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(s, e) for s, e in merged]


def clip(intervals, windows) -> list[tuple[float, float]]:
    """The parts of ``intervals`` that lie inside any of ``windows``."""
    out = []
    for lo, hi in windows:
        out.extend((max(s, lo), min(e, hi)) for s, e in intervals
                   if e > lo and s < hi)
    return out


def busy_s(ops, windows) -> float:
    return sum(e - s for s, e in
               union(clip([(s, e) for _, s, e in ops], windows)))


def idle_gaps(ops, window: tuple[float, float]) -> list[tuple[float, float]]:
    """The stretches of ``window`` in which no operation ran."""
    lo, hi = window
    gaps, at = [], lo
    for s, e in union(clip([(s, e) for _, s, e in ops], [window])):
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if hi > at:
        gaps.append((at, hi))
    return gaps


def op_sums(ops, windows) -> dict[str, float]:
    """Seconds per op name, each event clipped to the windows.  A parent op
    (a ``while``) holds its body's ops, so sums overlap; the union does not."""
    sums: dict[str, float] = {}
    for name, s, e in ops:
        inside = sum(b - a for a, b in clip([(s, e)], windows))
        if inside > 0:
            sums[name] = sums.get(name, 0.0) + inside
    return sums


def collective_s(ops, windows) -> float:
    return sum(v for name, v in op_sums(ops, windows).items()
               if COLLECTIVE.search(name))
