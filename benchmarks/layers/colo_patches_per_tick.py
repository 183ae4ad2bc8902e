"""colocation: the program's counter ``colocation_patches_total`` after the
window minus before its first tick (the benchmark's ``colo_tick`` spans carry
the reading before each), per tick."""


def read(ctx):
    from koordinator_tpu import metrics

    counter = getattr(metrics, "colocation_patches_total", None)
    before = [c.get("patches_before")
              for *_, c in ctx._window("colo_tick")]
    if counter is None or not before or before[0] is None:
        return None
    return (counter.value() - before[0]) / len(before)
