"""The trace reduction, on a hand-made trace and on a recorded one."""

from __future__ import annotations

import json
import os

import pytest

from benchmarks import trace_reduce
from benchmarks.context import TraceView

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def ns(seconds: float) -> float:
    return seconds * 1e9


HAND_MADE = {"planes": [
    {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [["jit_solve", ns(1.0), ns(2.0)]]},
        {"name": "XLA Ops", "events": [
            # a parent op holding two children, then a lone op, then one
            # that straddles the window's end
            ["while.1", ns(1.0), ns(2.0)],
            ["fusion.2", ns(1.2), ns(0.5)],
            ["all-reduce.3", ns(2.0), ns(0.25)],
            ["fusion.2", ns(5.0), ns(1.0)],
            ["copy.4", ns(9.5), ns(1.0)]]}]},
    {"name": "/device:TPU:1", "lines": [
        {"name": "XLA Ops", "events": [["fusion.2", ns(1.0), ns(1.0)]]}]},
    {"name": "/host:CPU", "lines": [
        {"name": "python", "events": [
            ["bench:window", ns(0.0), ns(10.0)],
            ["bench:wave_apply", ns(0.0), ns(0.9)],
            ["bench:solve_request", ns(0.9), ns(2.6)],
            ["bench:solve_request", ns(3.9), ns(2.6)],
            ["something_else", ns(0.0), ns(10.0)]]}]},
]}


def test_hand_made_union_idle_and_sums():
    view = TraceView(HAND_MADE)
    assert view.window == (0.0, 10.0)
    assert view.busiest == "/device:TPU:0"
    # [1, 3) + [5, 6) + [9.5, 10): the parent covers its children, the last
    # op is clipped at the window's end
    assert view.busiest_s == pytest.approx(3.5)
    assert view.mean_busy_s == pytest.approx((3.5 + 1.0) / 2)
    assert view.solve_busy_s == pytest.approx(3.0)
    assert view.collective_s == pytest.approx(0.25)
    ops = view.ops[view.busiest]
    assert trace_reduce.idle_gaps(ops, view.window) == [
        (0.0, 1.0), (3.0, 5.0), (6.0, 9.5)]
    sums = trace_reduce.op_sums(ops, [view.window])
    assert sums["fusion.2"] == pytest.approx(1.5)
    assert sums["copy.4"] == pytest.approx(0.5)
    broken = view.breakdown([("phase.Bind", 3.0, 4.5)])
    assert broken["device_ops"][0] == ["while.1", pytest.approx(2.0)]
    assert broken["idle_gaps"][0] == ["between_spans", pytest.approx(3.5)]
    assert broken["idle_gaps"][1] == ["solve_request:phase.Bind",
                                      pytest.approx(2.0)]
    assert broken["idle_gaps"][2] == ["wave_apply", pytest.approx(1.0)]


def brute_force_busy(ops, window, step=1e-6) -> float:
    """Busy time by marking a grid: shares no code with ``union``."""
    lo, hi = window
    cells = bytearray(int(round((hi - lo) / step)))
    for _, s, e in ops:
        a = max(0, int(round((s - lo) / step)))
        b = min(len(cells), int(round((e - lo) / step)))
        if b > a:
            cells[a:b] = b"\x01" * (b - a)
    return sum(cells) * step


def test_recorded_trace_agrees_with_a_grid_count():
    with open(os.path.join(DATA, "recorded_trace.json")) as f:
        recorded = json.load(f)
    view = TraceView(recorded["trace"])
    ops = view.ops[view.busiest]
    assert len(ops) == recorded["expect"]["ops"]
    assert view.busiest_s == pytest.approx(
        brute_force_busy(ops, view.window), abs=2e-6 * len(ops))
    assert view.busiest_s == pytest.approx(recorded["expect"]["busy_s"])
    assert 1 - view.busiest_s / view.window_s == pytest.approx(
        recorded["expect"]["idle_share"])
    sums = trace_reduce.op_sums(ops, [view.window])
    for name, seconds in recorded["expect"]["op_sums"].items():
        assert sums[name] == pytest.approx(seconds)
    gaps = trace_reduce.idle_gaps(ops, view.window)
    assert sum(e - s for s, e in gaps) + view.busiest_s == pytest.approx(
        view.window_s)
