"""transport: what a ``STATE_PUSH`` frame spends between the two threads: the
client's ``rpc.wait`` under ``rpc.call.STATE_PUSH`` minus the server's
``rpc.STATE_PUSH``, per frame (socket hops and thread wake-ups).  ``None``
where the client is in another process."""

from benchmarks import program_spans


def read(ctx):
    recs = program_spans.records(ctx)
    waited, _ = program_spans.total(
        recs, lambda r: r["name"] == "rpc.wait"
        and r["parent"] == "rpc.call.STATE_PUSH")
    served, frames = program_spans.total(
        recs, lambda r: r["name"] == "rpc.STATE_PUSH")
    if not frames or not waited:
        return None
    return (waited - served) * 1e3 / frames
