"""entry: ``solver_load_seconds_total`` over every ``fn``: the wall of the
calls that grew a jitted entry's cache (trace, lower, compile or cache load,
dispatch).  A load inside the window is a recompile and fails the run, so
the total is set-up's."""


def read(ctx):
    from koordinator_tpu import metrics

    counter = getattr(metrics, "solver_load_seconds", None)
    loads = counter.items() if counter is not None else []
    return sum(v for _, v in loads) if loads else None
