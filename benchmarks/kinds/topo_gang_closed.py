"""Training gangs that come and go on a labelled fabric: one closed loop,
the same work on every seed.

Set-up loads the nodes with their pod and rack labels and their devices
(``node_upsert`` frames), runs ``settle_cycles`` whole cycles of the
schedule (the first solve is ``first_round_s``) and then ``warm_cycles``
more: every program of the window's shapes has then run on a state that has
bound and released, after the settle, so none compiles in the window.

One cycle, one client, every event a ``STATE_PUSH`` frame on the socket (the
next step starts when the last one has answered):

1. ``depart``: the gangs whose lifetime ends leave (``pod_remove`` of every
   member);
2. ``arrive_push``: the schedule's gangs, each record (``gang``) before its
   members (``pod_add``); these are the pods offered;
3. ``solve_request``: one round on the socket.
"""

from __future__ import annotations

import time

from benchmarks.layers import topo_plan_fail_share

#: 608 pods in the 1,024-row bucket: the exact greedy scan
ROUND_PATHS = "greedy"


def cycle(dep, params: dict, spans) -> dict:
    dep.cycle_no += 1
    with spans.span("depart", plans_before=topo_plan_fail_share.outcomes()):
        left = dep.depart()
    with spans.span("arrive_push"):
        offered = dep.arrive(params["schedule"])
    with spans.span("solve_request", topo_tree=dep.tree_shape()):
        bound = dep.solve()
    return {"left": left, "offered": offered, "bound": bound,
            "busy_racks": dep.busy_racks(), "half_racks": dep.half_racks()}


def setup(dep, params: dict, spans) -> dict:
    with spans.span("load_nodes"):
        dep.load_nodes()
    settled = []
    with spans.span("settle"):
        for _ in range(params["settle_cycles"]):
            settled.append(cycle(dep, params, spans))
    with spans.span("warm_cycles"):
        for _ in range(params["warm_cycles"]):
            settled.append(cycle(dep, params, spans))
    return {"settled": settled}


def window(dep, params: dict, state: dict, deadline: float, spans) -> dict:
    done = []
    t_close = time.perf_counter()
    most = params.get("max_cycles", float("inf"))
    while time.perf_counter() < deadline and len(done) < most:
        done.append(cycle(dep, params, spans))
        t_close = time.perf_counter()
    return {"t_close": t_close, "cycles": len(done),
            "offered_by_cycle": [c["offered"] for c in done],
            "bound_by_cycle": [c["bound"] for c in done],
            "left_by_cycle": [c["left"] for c in done],
            "busy_racks_by_cycle": [c["busy_racks"] for c in done],
            "half_busy_racks": max((c["half_racks"] for c in done),
                                   default=0),
            "busy_racks_in_settle": [c["busy_racks"]
                                     for c in state["settled"]]}
