"""live DELTA fan-out: what a connection's sender thread pays per event to
take the run after its cursor from the delta log, pack and encode it: busy
time of the program's ``sync.frame`` spans inside the window over their
members (n = events carried).  The ready single-event frame of a watcher
that keeps up is built by the committer, inside ``sync.store``, and is not
in it.  ``None`` where no run was built."""

from benchmarks import program_spans


def read(ctx):
    busy, events = program_spans.total(
        program_spans.records(ctx), lambda r: r["name"] == "sync.frame")
    return busy * 1e3 / events if events else None
