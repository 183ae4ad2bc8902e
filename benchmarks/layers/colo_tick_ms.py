"""colocation: busy time of the program's ``colo.tick`` spans (one
``ColocationLoop.tick``: records, reconcile, pushes) inside the window, mean
per tick."""

from benchmarks import program_spans


def read(ctx):
    busy, ticks = program_spans.total(
        program_spans.records(ctx), lambda r: r["name"] == "colo.tick")
    return busy * 1e3 / ticks if ticks else None
