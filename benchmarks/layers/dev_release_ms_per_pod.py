"""deviceshare: busy time of the program's ``release.devices`` spans (a
leaving pod's devices given back in the host books, under
``release.fine_grained``; the device op of all of a round's releases is one
fold at the next read of the state) inside the window, per pod."""

from benchmarks import program_spans


def read(ctx):
    busy, pods = program_spans.total(
        program_spans.records(ctx), lambda r: r["name"] == "release.devices")
    return busy * 1e3 / pods if pods else None
