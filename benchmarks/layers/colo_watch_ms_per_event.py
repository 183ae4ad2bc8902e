"""colocation: busy time of the program's ``colo.watch`` spans (one node
delta applied to the manager's view, on its watch's reader thread) inside
the window, per delta."""

from benchmarks import program_spans


def read(ctx):
    busy, events = program_spans.total(
        program_spans.records(ctx), lambda r: r["name"] == "colo.watch")
    return busy * 1e3 / events if events else None
