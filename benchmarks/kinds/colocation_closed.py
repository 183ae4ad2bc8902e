"""The colocation loop beside the serving scheduler: one closed loop.

Set-up loads the nodes with no batch or mid allocatable, binds the online
pods (``fill_waves`` rounds of the drain's arrival path with no watcher
connected, which also load the full programs), brings the koordlets up over
``bring_up_intervals`` report intervals (each node's interval drawn from the
seed: the time-gap rule then finds a sixth of the cluster due at every tick,
not all of it at every sixth; the first tick dials, bootstraps the manager's
watch and patches every node), fills the batch allocatable with Spark jobs,
loads the next pod bucket up once (``warm_overflow_standing``: a cycle whose
arrivals and leftovers pass a power of two must not compile in the window),
then runs whole cycles until the patches of a tick have settled: at least
``settle_min`` (the time-gap rule bites only after
``updateTimeThresholdSeconds``), and on until the last ``settle_window`` ticks
differ by under ``settle_tolerance`` of the largest, at most ``settle_max``.

One cycle, one client (the next step starts when the last one has
answered); the cell's clock steps one report interval:

1. ``usage_wave``: every node reports its five usage vectors and the report
   time (the sync service's ``update_node_usage``), in stretches after each
   of which the manager's watch is waited for (``watch_catchup``);
2. ``colo_tick``: one ``colocation_loop.tick()``;
3. ``depart``: the pods of the jobs that end leave (``remove_pod``);
4. ``admit_apply``: ``jobs_per_cycle`` new Spark jobs go through the
   manager's webhooks and ``add_pod``; these are the pods offered;
5. ``solve_request``: one round on the socket.

``ROUND_PATHS`` names a cycle's round by position; the mix says which solve
path it takes (``paths``).
"""

from __future__ import annotations

import time

ROUND_PATHS = ["round"]


def patches_total():
    """The program's counter of patches pushed; None in a program that
    keeps none."""
    from koordinator_tpu import metrics

    counter = getattr(metrics, "colocation_patches_total", None)
    return None if counter is None else counter.value()


def cycle(dep, params: dict, spans) -> dict:
    dep.step_clock()
    with spans.span("usage_wave", nodes=dep.sizes["nodes"]):
        dep.usage_wave(spans)
    with spans.span("colo_tick", patches_before=patches_total()):
        patches = dep.tick()
    with spans.span("depart"):
        left = dep.depart()
    with spans.span("admit_apply"):
        pods = dep.spark_jobs(params["jobs_per_cycle"],
                              dep.job_lifetimes(params["jobs_per_cycle"]))
        dep.offer(pods)
    with spans.span("solve_request"):
        dep.solve()
    return {"patches": patches, "left": left, "arrived": len(pods),
            "squeezed": dep.squeezed_nodes()}


def setup(dep, params: dict, spans) -> dict:
    dep.expected_paths = dict(params["paths"])
    with spans.span("load_nodes"):
        dep.load_nodes()
    with spans.span("warm_up"):
        dep.fill(params)
    with spans.span("bring_up"):
        ticks = []
        for interval in range(dep.config["clock"]["bring_up_intervals"]):
            dep.step_clock()
            dep.usage_wave(groups=interval + 1)
            ticks.append(dep.tick())
    with spans.span("fill_batch"):
        dep.fill_batch(params)
    with spans.span("warm_overflow"):
        # every row is dirty after a usage wave, as in a cycle: the round
        # over the next pod bucket up takes the window's path (and no
        # interval passes without its tick: the time-gap rule counts
        # seconds)
        dep.step_clock()
        dep.usage_wave()
        dep.tick()
        dep.set_standing(params["warm_overflow_standing"])
        dep.solve()
        dep.set_standing(dep.sizes["standing"])
    with spans.span("settle"):
        settled = []
        back = params["settle_window"]
        while len(settled) < params["settle_max"]:
            settled.append(cycle(dep, params, spans))
            last = [c["patches"] for c in settled[-back:]]
            if (len(settled) >= max(params["settle_min"], back)
                    and max(last) - min(last)
                    <= params["settle_tolerance"] * max(last)):
                break
    return {"settled": settled, "bring_up_patches": ticks}


def window(dep, params: dict, state: dict, deadline: float, spans) -> dict:
    done = []
    t_close = time.perf_counter()
    most = params.get("max_cycles", float("inf"))
    while time.perf_counter() < deadline and len(done) < most:
        done.append(cycle(dep, params, spans))
        t_close = time.perf_counter()
    return {"t_close": t_close, "cycles": len(done),
            "patches_per_tick": [c["patches"] for c in done],
            "batch_squeezed_nodes": [c["squeezed"] for c in done],
            "left_by_cycle": [c["left"] for c in done],
            "arrived_by_cycle": [c["arrived"] for c in done],
            "patches_in_settle": [c["patches"] for c in state["settled"]],
            "patches_in_bring_up": state["bring_up_patches"],
            **dep.report}
