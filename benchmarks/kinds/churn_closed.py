"""Sustained arrivals and departures through the socket: one closed loop.

Set-up loads the nodes, binds one wave and runs ``settle`` cycles (right
after a drain every node is dirty, and the incremental programs compile
once).  The window runs cycles until ``--seconds`` are up (or ``max_cycles``, which
a mix sets for its traced run only).  One cycle, every
frame a synchronous ``STATE_PUSH`` from the one client: ``usage_nodes``
``node_usage`` frames, ``leave`` ``pod_remove`` frames (bound pods on
distinct nodes not touched this cycle), ``arrive`` ``pod_add`` frames, then
one ``SOLVE_REQUEST``.  The next cycle starts when the response is held:
one client, closed loop.
"""

from __future__ import annotations

import time

from benchmarks.reference import generators

ROUND_PATHS = "incremental"


def cycle(dep, params: dict, spans) -> None:
    books, rng = dep.books, dep.rng
    names = books.node_names
    touched = rng.choice(len(names), params["usage_nodes"], replace=False)
    new_usage = generators.make_usage(rng, books.alloc[touched])
    busy = {names[row] for row in touched}
    bound = list(books.bound)
    leaving = []
    for i in rng.permutation(len(bound)):
        node = books.bound[bound[i]]
        if node not in busy:
            busy.add(node)
            leaving.append(bound[i])
            if len(leaving) == params["leave"]:
                break
    arriving = dep.wave(params["arrive"])
    with spans.span("push", frames=len(touched), what="node_usage"):
        for j, row in enumerate(touched):
            books.usage[row] = new_usage[j]
            dep.push({"kind": "node_usage", "name": names[row]},
                     {"usage": new_usage[j]})
    with spans.span("push", frames=len(leaving), what="pod_remove"):
        for pod in leaving:
            dep.push({"kind": "pod_remove", "name": pod})
            books.leave(pod)
    with spans.span("push", frames=len(arriving), what="pod_add"):
        for pod in arriving:
            dep.push_pod(pod)
    with spans.span("solve_request"):
        dep.solve()


def setup(dep, params: dict, spans) -> dict:
    with spans.span("load_nodes"):
        dep.load_nodes()
    with spans.span("warm_up"):
        dep.warm_up(params)
    with spans.span("settle"):
        for _ in range(params["settle"]):
            cycle(dep, params, spans)
    return {}


def window(dep, params: dict, state: dict, deadline: float, spans) -> dict:
    cycles, first = 0, len(spans.records)
    t_close = time.perf_counter()
    most = params.get("max_cycles", float("inf"))
    while time.perf_counter() < deadline and cycles < most:
        cycle(dep, params, spans)
        t_close = time.perf_counter()
        cycles += 1
    # ms a frame by what the frame carried, for the lines before the last
    frame_ms = {}
    for what in ("node_usage", "pod_remove", "pod_add"):
        mine = [(t1 - t0, c["frames"]) for name, t0, t1, c
                in spans.records[first:]
                if name == "push" and c["what"] == what]
        frames = sum(n for _, n in mine)
        if frames:
            frame_ms[what] = sum(s for s, _ in mine) * 1e3 / frames
    return {"t_close": t_close, "cycles": cycles, "frame_ms": frame_ms}
