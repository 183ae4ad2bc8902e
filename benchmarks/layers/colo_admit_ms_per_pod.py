"""colocation: busy time of the program's ``colo.admit`` spans (one pod
through the mutating webhook: profile match, QoS and priority, batch
resource translation) inside the window, per pod."""

from benchmarks import program_spans


def read(ctx):
    busy, pods = program_spans.total(
        program_spans.records(ctx), lambda r: r["name"] == "colo.admit")
    return busy * 1e3 / pods if pods else None
