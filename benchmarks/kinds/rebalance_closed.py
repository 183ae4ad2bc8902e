"""Load-aware rebalancing beside the serving scheduler: one closed loop.

Set-up loads the nodes, fills the cluster (``fill_waves`` rounds of the
drain's arrival path, which also load the full programs), heats
``hot_nodes_start`` nodes, runs ``anomaly_rounds - 1`` usage waves +
descheduling rounds so that those nodes stand one round before the anomaly
gate, then ``settle`` whole cycles: every program and bucket the window
uses is compiled or loaded there, and the mix of fresh and half-drained hot
nodes is the steady one when the window opens.  The replacement round's
refresh is padded to a power of two of the rows touched since the last
round, and a steady cycle's count can sit near one: in the last settle
cycle ``warm_dirty_nodes`` more nodes report once more before that round,
so the next size up is loaded too.

One cycle, in process, one client (the next step starts when the last one
has answered):

1. ``usage_wave``: every node reports its usage (the sync service's
   ``update_node_usage``); ``heat_per_cycle`` more nodes run hot from now on;
2. ``desched_balance``: one ``Descheduler.run_once()``;
3. ``migrate_reconcile``: one ``MigrationController.reconcile()``: arbitrate,
   ONE reservation round (under a ``solve_request`` span), evict;
4. ``replace_apply``: one ``add_pod`` per victim gone, the pod its workload's
   controller creates; these are the pods offered;
5. ``solve_request``: one round; the replacements bind into their
   reservations.

``ROUND_PATHS`` names a cycle's rounds by position; which solve path each
takes is the mix's to say (``paths``), since it hangs on how many jobs a
cycle runs against the standing queue.
"""

from __future__ import annotations

import time

ROUND_PATHS = ["reserve", "replace"]


def reserve_rounds_total():
    """The program's counter of reservation rounds; None in a program that
    keeps none."""
    from koordinator_tpu import metrics

    counter = getattr(metrics, "migration_reserve_rounds", None)
    return None if counter is None else counter.value()


def cycle(dep, params: dict, spans, heat: int, touch: int = 0) -> dict:
    with spans.span("usage_wave", nodes=dep.sizes["nodes"]):
        dep.usage_wave(heat)
    with spans.span("desched_balance"):
        victims = dep.deschedule()
    with spans.span("migrate_reconcile",
                    reserve_rounds_before=reserve_rounds_total()):
        gone = dep.reconcile(spans)
    with spans.span("replace_apply", pods=len(gone)):
        dep.replace(gone)
    dep.report_again(touch)
    with spans.span("solve_request"):
        dep.solve()
    dep.note_binds()
    return {"victims": victims, "migrated": len(gone)}


def setup(dep, params: dict, spans) -> dict:
    dep.expected_paths = dict(params["paths"])
    with spans.span("load_nodes"):
        dep.load_nodes()
    with spans.span("warm_up"):
        dep.fill(params)
    with spans.span("gate"):
        for i in range(dep.defaults["anomaly_rounds"] - 1):
            dep.usage_wave(params["hot_nodes_start"] if i == 0 else 0)
            dep.deschedule()
    with spans.span("settle"):
        last = params["settle"] - 1
        settled = [cycle(dep, params, spans, params["heat_per_cycle"],
                         params.get("warm_dirty_nodes", 0) if i == last else 0)
                   for i in range(params["settle"])]
    return {"settled": settled}


def window(dep, params: dict, state: dict, deadline: float, spans) -> dict:
    done = []
    t_close = time.perf_counter()
    most = params.get("max_cycles", float("inf"))
    while time.perf_counter() < deadline and len(done) < most:
        done.append(cycle(dep, params, spans, params["heat_per_cycle"]))
        t_close = time.perf_counter()
    return {"t_close": t_close, "cycles": len(done),
            "victims_by_cycle": [c["victims"] for c in done],
            "migrated_by_cycle": [c["migrated"] for c in done],
            "migrated_in_settle": [c["migrated"] for c in state["settled"]]}
