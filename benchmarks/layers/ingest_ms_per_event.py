"""transport: busy time of the program's root ingest spans inside the window
per event: the server's ``rpc.STATE_PUSH`` (decode -> handler -> reply
queued) where frames arrive, else the in-process ``sync.store``."""

from benchmarks import program_spans


def read(ctx):
    recs = program_spans.records(ctx)
    busy, events = program_spans.total(
        recs, lambda r: r["name"] == "rpc.STATE_PUSH"
        or (r["name"] == "sync.store" and r["parent"] != "rpc.STATE_PUSH"))
    return busy * 1e3 / events if events else None
