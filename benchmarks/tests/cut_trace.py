"""Cut a small recorded piece out of a dumped trace, for the tests.

    python3 benchmarks/tests/cut_trace.py <dump.json> <out.json> [max_ops]

``dump.json`` is what ``run.py --trace 1 --dump-trace`` wrote on the chip.
The piece keeps the window's start, the benchmark's spans and the first
``max_ops`` device operations of each device in the window; its window ends
where the last kept operation ends.  ``expect`` holds what the reduction
read off the piece when it was cut: the test pins those numbers, and checks
the busy union against a count that shares no code with it.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks import trace_reduce  # noqa: E402
from benchmarks.context import TraceView  # noqa: E402


def cut(trace: dict, max_ops: int) -> dict:
    (_, w0, w1), = [s for s in trace_reduce.bench_spans(trace)
                    if s[0] == "window"]
    end_ns = w0 * 1e9
    planes = []
    for plane in trace["planes"]:
        if not trace_reduce.DEVICE_PLANE.match(plane["name"]):
            continue
        lines = []
        for line in plane["lines"]:
            inside = sorted((e for e in line["events"]
                             if w0 * 1e9 <= e[1] and e[1] + e[2] <= w1 * 1e9),
                            key=lambda e: e[1])[:max_ops]
            if inside:
                end_ns = max(end_ns, max(e[1] + e[2] for e in inside))
                lines.append({"name": line["name"], "events": inside})
        planes.append({"name": plane["name"], "lines": lines})
    spans = [["bench:" + name, max(s, w0) * 1e9,
              (min(e, end_ns / 1e9) - max(s, w0)) * 1e9]
             for name, s, e in trace_reduce.bench_spans(trace)
             if name != "window" and e > w0 and s < end_ns / 1e9]
    spans.append(["bench:window", w0 * 1e9, end_ns - w0 * 1e9])
    planes.append({"name": "/host:CPU",
                   "lines": [{"name": "python", "events": spans}]})
    return {"planes": planes}


def main(argv: list[str]) -> int:
    with open(argv[0]) as f:
        piece = cut(json.load(f), int(argv[2]) if len(argv) > 2 else 1500)
    view = TraceView(piece)
    ops = view.ops[view.busiest]
    sums = trace_reduce.op_sums(ops, [view.window])
    top = sorted(sums.items(), key=lambda kv: -kv[1])[:5]
    expect = {"ops": len(ops), "busy_s": view.busiest_s,
              "idle_share": 1 - view.busiest_s / view.window_s,
              "op_sums": dict(top)}
    with open(argv[1], "w") as f:
        json.dump({"trace": piece, "expect": expect}, f)
    print(json.dumps({k: v for k, v in expect.items() if k != "op_sums"}),
          os.path.getsize(argv[1]), "bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
