"""descheduler: busy time of the program's ``desched.round`` spans (one
LowNodeLoad balance: staging, selection, submitting the jobs) inside the
window, mean per descheduling round."""

from benchmarks import program_spans


def read(ctx):
    busy, rounds = program_spans.total(
        program_spans.records(ctx), lambda r: r["name"] == "desched.round")
    return busy * 1e3 / rounds if rounds else None
