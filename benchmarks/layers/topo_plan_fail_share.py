"""topology plan: of the gangs with a topology requirement planned in the
window, the share that found no domain to hold them (and backed off whole).
The program's counter ``topology_plans_total{outcome}`` after the window
minus before its first cycle (the benchmark's ``depart`` spans carry the
reading before each).  ``fabric_gang_cycle``'s traffic has a plan for every
gang it offers, so it reads 0 unless the program is at fault."""


def outcomes():
    """{outcome: count} of the program's counter; None in a program that
    keeps none."""
    from koordinator_tpu import metrics

    counter = getattr(metrics, "topology_plans", None)
    if counter is None:
        return None
    return {labels["outcome"]: value for labels, value in counter.items()}


def read(ctx):
    before = [c.get("plans_before") for *_, c in ctx._window("depart")]
    after = outcomes()
    if after is None or not before or before[0] is None:
        return None
    moved = {k: v - before[0].get(k, 0) for k, v in after.items()}
    total = sum(moved.values())
    return 100.0 * moved.get("no_fit", 0) / total if total else None
