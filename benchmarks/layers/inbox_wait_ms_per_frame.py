"""transport: how long a ``STATE_PUSH`` frame stood in its connection's
inbox, between the reader thread that stamped its arrival and the dispatch
worker that opened its span: the program's wait observation
``rpc.inbox.STATE_PUSH`` (``koordinator_tpu.timeline``: every doc's
``waits`` map, ``{name: {n, wait_s, max_s}}``), ``wait_s`` / ``n`` over the
window's docs.  A doc that straddles an end of the window is cut pro rata,
as ``program_spans.records`` cuts a run.  ``None`` where the program keeps
no such observations (the parent of PR 34: its docs have no ``waits``)."""


def observed(ctx, name: str):
    """(seconds waited, pieces of work) under ``name`` inside the window,
    or ``None`` with nothing to read.  ``ctx.timeline_docs``, where a test
    sets it, stands in for the recorder's ring."""
    docs = getattr(ctx, "timeline_docs", None)
    if docs is None:
        from koordinator_tpu import timeline

        docs = timeline.RECORDER.cycles(64)
    waited = pieces = 0.0
    for doc in docs:
        row = doc.get("waits", {}).get(name)
        wall = doc.get("wall_s", 0.0)
        if row is None or wall <= 0.0:
            continue
        lo = max(doc["start"], ctx.t_open)
        hi = min(doc["start"] + wall, ctx.t_close)
        if hi <= lo:
            continue
        share = (hi - lo) / wall
        waited += row["wait_s"] * share
        pieces += row["n"] * share
    return (waited, pieces) if pieces else None


def ms_per_piece(ctx, name: str):
    found = observed(ctx, name)
    return None if found is None else found[0] * 1e3 / found[1]


def read(ctx):
    return ms_per_piece(ctx, "rpc.inbox.STATE_PUSH")
