"""deviceshare: of the device-requesting proposals that reached the device
stage in the window, the share that was accepted on the node's aggregate
rows and then undone because a pod ahead of it in the same round took the
device (it proposes again next round).  The program's counter
``deviceshare_grants_total{outcome}`` after the window minus before its first
cycle (the benchmark's ``device_events`` spans carry the reading before
each)."""


def outcomes():
    """{outcome: count} of the program's counter; None in a program that
    keeps none."""
    from koordinator_tpu import metrics

    counter = getattr(metrics, "deviceshare_grants", None)
    if counter is None:
        return None
    return {labels["outcome"]: value for labels, value in counter.items()}


def read(ctx):
    before = [c.get("outcomes_before")
              for *_, c in ctx._window("device_events")]
    after = outcomes()
    if after is None or not before or before[0] is None:
        return None
    moved = {k: v - before[0].get(k, 0) for k, v in after.items()}
    total = sum(moved.values())
    return 100.0 * moved.get("lost_race", 0) / total if total else None
