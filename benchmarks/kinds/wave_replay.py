"""Successive waves into the cluster's headroom: fixed work, nothing leaves.

Set-up loads the nodes and drains one warm-up wave (``first_round_s`` is its
first round).  The window replays ``waves`` further waves, each: in-process
arrival of every pod of the wave through the sync service's mutator, then
solve requests until every pod of the wave is bound or carries a diagnosis
(one round, unless an answer left pods with neither; at most
``max_rounds``).  Pods a wave leaves unplaced stay pending into the next
wave's round.  No wave starts after ``--seconds`` have run out.
"""

from __future__ import annotations

import time

ROUND_PATHS = "full"


def setup(dep, params: dict, spans) -> dict:
    with spans.span("load_nodes"):
        dep.load_nodes(params.get("replays", 1))
    # drawn before the window so that the generator's own cost is not in it
    plan = [dep.wave() for _ in range(params["waves"])]
    with spans.span("warm_up"):
        dep.warm_up(params, plan)
    return {"plan": plan}


def window(dep, params: dict, state: dict, deadline: float, spans) -> dict:
    books = dep.books
    replays = params.get("replays", 1)
    waves = 0
    t_close = time.perf_counter()
    for i, pods in enumerate(state["plan"] * replays):
        if time.perf_counter() >= deadline:
            break
        if i and i % len(state["plan"]) == 0:
            dep.next_cluster()
        with spans.span("wave_apply", pods=len(pods)):
            dep.offer(pods)
        for _ in range(params["max_rounds"]):
            before = books.undiagnosed
            with spans.span("solve_request"):
                dep.solve()
            if books.undiagnosed == before:
                break
        t_close = time.perf_counter()
        waves += 1
    return {"t_close": t_close, "waves_done": waves,
            "waves_asked": params["waves"] * replays}
