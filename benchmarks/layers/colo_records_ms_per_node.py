"""colocation: busy time of the program's ``colo.records`` spans (the
manager's view turned into one ``NodeRecord`` per node) inside the window,
per node."""

from benchmarks import program_spans


def read(ctx):
    busy, nodes = program_spans.total(
        program_spans.records(ctx), lambda r: r["name"] == "colo.records")
    return busy * 1e3 / nodes if nodes else None
