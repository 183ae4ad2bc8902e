"""deviceshare: busy time of the program's ``sync.node_devices`` spans (a
Device-CR refresh applied: one row of the device table rewritten) inside the
window, per event."""

from benchmarks import program_spans


def read(ctx):
    busy, events = program_spans.total(
        program_spans.records(ctx),
        lambda r: r["name"] == "sync.node_devices")
    return busy * 1e3 / events if events else None
