"""deviceshare: busy time of the program's ``bind.devices`` spans (a round's
device grants written into the host books in one step, under
``phase.Bind``) inside the window, per grant recorded."""

from benchmarks import program_spans


def read(ctx):
    busy, grants = program_spans.total(
        program_spans.records(ctx), lambda r: r["name"] == "bind.devices")
    return busy * 1e3 / grants if grants else None
