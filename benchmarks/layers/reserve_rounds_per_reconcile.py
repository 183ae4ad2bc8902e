"""migration: the program's counter ``migration_reserve_rounds_total``
after the window minus before its first reconcile (the benchmark's
``migrate_reconcile`` spans carry the reading before each), per reconcile.
Reads 1: one batched reservation round, however many jobs."""


def read(ctx):
    from koordinator_tpu import metrics

    counter = getattr(metrics, "migration_reserve_rounds", None)
    before = [c.get("reserve_rounds_before")
              for *_, c in ctx._window("migrate_reconcile")]
    if counter is None or not before or before[0] is None:
        return None
    return (counter.value() - before[0]) / len(before)
