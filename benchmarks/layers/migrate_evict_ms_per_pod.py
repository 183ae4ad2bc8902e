"""migration: busy time of the program's ``migrate.evict`` spans (every
running job's pod leaves through ``Scheduler.delete_pod``; ``release.*``
below it) inside the window, per pod."""

from benchmarks import program_spans


def read(ctx):
    busy, pods = program_spans.total(
        program_spans.records(ctx), lambda r: r["name"] == "migrate.evict")
    return busy * 1e3 / pods if pods else None
