"""pending queue: p95 over every pod bound by a round committed inside the
window of the time from its enqueue (``Scheduler.enqueue``: the pod lands in
the pending queue) to the start of the round that bound it.  The program's
own journey ledger (``koordinator_tpu.journey``), its per-round slices cut
to [``ctx.t_open``, ``ctx.t_close``] and all (tenant, qos) series of the
stage merged (a sketch: within 1 % of the sample's value).  ``None`` where
the ledger is off, bound nothing in the window, or cannot be cut by time
(the parent of PR 34)."""


def stage_quantile_ms(ctx, stage: str, q: float):
    from koordinator_tpu import journey

    try:
        doc = journey.LEDGER.snapshot_doc(since_perf=ctx.t_open,
                                          until_perf=ctx.t_close)
    except TypeError:
        return None
    merged = journey.DDSketch()
    for row in doc["series"]:
        if row["stage"] == stage:
            merged.merge(journey.DDSketch.from_doc(row["sketch"]))
    value = merged.quantile(q)
    return None if value is None else value * 1e3


def read(ctx):
    return stage_quantile_ms(ctx, "queue_wait", 0.95)
