"""topology plan: busy time of the program's ``topo.plan`` spans (a round's
network-topology gang plans, one after another, each charged before the
next: the device prep, the host walk and the charge) inside the window, per
gang planned."""

from benchmarks import program_spans


def read(ctx):
    busy, gangs = program_spans.total(
        program_spans.records(ctx), lambda r: r["name"] == "topo.plan")
    return busy * 1e3 / gangs if gangs else None
