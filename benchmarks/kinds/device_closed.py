"""Fractional and whole-GPU jobs that come and go: one closed loop.

Set-up loads the nodes with their device inventories and the standing pods
(pods the aggregate GPU rows hold and no device does: none binds), fills the
cluster with waves of the mix until ``gpu_fill_target`` of its GPU core is
granted (the first of these rounds is ``first_round_s``), spreads the
remaining lifetimes of what is bound, and runs whole cycles until
``gpu_core_allocated_share`` of ``settle_window`` cycles in a row stays within
``settle_tolerance`` (at most ``settle_max`` cycles).

Only then does it load every pod bucket the window can reach: one round each
with ``warm_standing`` pods in the queue that fit no node.  The pods that wait
after a cycle fit none either (the round's exact scan left them over, and
nothing has moved since), so the standing pods are topped up to the count over
them, and a round of s such pods runs the round's programs, the leftover scan
and the diagnosis at the bucket of s.  It comes after the fill because a
jitted program's cache is keyed on which arguments are committed to the device
as well as on their shapes: the snapshot's folds place their deltas like the
state, what is computed from a placed array is committed too, so a cluster
that has bound and released calls another entry than one that has only been
loaded, and a bucket loaded before the fill compiles again when the window
first reaches it.  ``resettle`` whole cycles then bring the queue and the
candidate cache back to a cycle's sizes.

One cycle, one client (the next step starts when the last one has answered),
arrivals and departures through the sync service in process:

1. ``depart``: the pods whose lifetime ends leave (``remove_pod``), and the
   pods pending past their owner's patience are withdrawn;
2. ``device_events``: ``device_events`` Device-CR refreshes
   (``update_node_devices``): a device turns unhealthy, one recovers;
3. ``arrive_apply``: ``arrive`` pods of the mix (``add_pod``); these are the
   pods offered;
4. ``solve_request``: one round on the socket.
"""

from __future__ import annotations

import time

from benchmarks.layers import dev_lost_race_share

#: most rows are dirty after a cycle's departures: a full round
ROUND_PATHS = "full"


def cycle(dep, params: dict, spans) -> dict:
    dep.cycle_no += 1
    with spans.span("depart"):
        left = dep.depart()
        withdrawn = dep.give_up()
    with spans.span("device_events",
                    outcomes_before=dev_lost_race_share.outcomes()):
        events = dep.device_events(params["device_events"])
    with spans.span("arrive_apply"):
        pods = dep.wave(params["arrive"])
        dep.offer(pods)
    with spans.span("solve_request"):
        bound = dep.solve()
    return {"left": left, "withdrawn": withdrawn, "events": events,
            "arrived": len(pods), "bound": bound,
            "share": dep.gpu_core_allocated_share()}


def setup(dep, params: dict, spans) -> dict:
    with spans.span("load_nodes"):
        dep.load_nodes()
    with spans.span("warm_up"):
        dep.set_standing(dep.sizes["standing"])
        fill = []
        while dep.gpu_core_allocated_share() < params["gpu_fill_target"]:
            if len(fill) == params["fill_max_waves"]:
                raise RuntimeError(
                    f"fill stopped at {dep.gpu_core_allocated_share():.3f} "
                    f"of the GPU core after {len(fill)} waves")
            dep.cycle_no += 1
            dep.give_up()
            dep.offer(dep.wave(params["fill_wave"]), counts=False)
            t0 = time.perf_counter()
            dep.solve()
            if not fill:
                dep.first_round_s = time.perf_counter() - t0
            fill.append(dep.gpu_core_allocated_share())
        dep.spread_lifetimes()
    with spans.span("settle"):
        settled = []
        back = params["settle_window"]
        while len(settled) < params["settle_max"]:
            settled.append(cycle(dep, params, spans))
            last = [c["share"] for c in settled[-back:]]
            if (len(settled) >= back
                    and max(last) - min(last) <= params["settle_tolerance"]):
                break
    with spans.span("warm_buckets"):
        warmed = []
        for fit_none in params["warm_standing"]:
            waiting = len(dep.books.pending) - dep.standing_now
            if fit_none < waiting:
                # a bucket under what waits: no round of this run is that
                # small while this many wait
                continue
            dep.set_standing(fit_none - waiting)
            dep.solve()
            warmed.append(len(dep.books.pending))
        dep.set_standing(dep.sizes["standing"])
        for _ in range(params["resettle"]):
            settled.append(cycle(dep, params, spans))
    return {"fill": fill, "settled": settled, "warmed": warmed}


def window(dep, params: dict, state: dict, deadline: float, spans) -> dict:
    done = []
    t_close = time.perf_counter()
    most = params.get("max_cycles", float("inf"))
    while time.perf_counter() < deadline and len(done) < most:
        done.append(cycle(dep, params, spans))
        t_close = time.perf_counter()
    return {"t_close": t_close, "cycles": len(done),
            "left_by_cycle": [c["left"] for c in done],
            "withdrawn_by_cycle": [c["withdrawn"] for c in done],
            "bound_by_cycle": [c["bound"] for c in done],
            "device_events": sum(c["events"] for c in done),
            "share_in_fill": state["fill"],
            "share_in_settle": [c["share"] for c in state["settled"]],
            "pending_in_warm_rounds": state["warmed"],
            **dep.reported()}
