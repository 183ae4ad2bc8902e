"""migration: busy time of the program's ``migrate.reserve`` spans (one
reservation per job, ONE scheduling round for all, one read per job) inside
the window, per job."""

from benchmarks import program_spans


def read(ctx):
    busy, jobs = program_spans.total(
        program_spans.records(ctx), lambda r: r["name"] == "migrate.reserve")
    return busy * 1e3 / jobs if jobs else None
