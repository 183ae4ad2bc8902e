"""colocation: busy time of the program's ``colo.push`` spans (every patch
of a tick pushed to the scheduler as one synchronous ``STATE_PUSH``) inside
the window, per patch."""

from benchmarks import program_spans


def read(ctx):
    busy, patches = program_spans.total(
        program_spans.records(ctx), lambda r: r["name"] == "colo.push")
    return busy * 1e3 / patches if patches else None
