"""live DELTA fan-out: how long what a commit queued for a watcher (a ready
single-event frame, or the notice that there is news) stood in the
connection's outbox before its sender thread reached it, per item: the
program's wait observation ``rpc.outbox.DELTA``, ``wait_s`` / ``n`` over the
window's docs.  Building the run's frame once the sender is there is
``fanout_ms_per_event``'s time.  ``None`` where the program keeps no such
observations."""

from benchmarks.layers import inbox_wait_ms_per_frame


def read(ctx):
    return inbox_wait_ms_per_frame.ms_per_piece(ctx, "rpc.outbox.DELTA")
