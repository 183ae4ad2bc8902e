"""transport: how long the reply to a ``STATE_PUSH`` frame (an ``ACK``)
stood in its connection's outbox, between the handler that queued it
(``_Conn.send``) and the sender thread that took it off the queue to write
it: the program's wait observation ``rpc.outbox.ACK``, ``wait_s`` / ``n``
over the window's docs.  What stands before it in the same queue is the
frame's own echo on the live DELTA stream.  No cell sends a ``PING``, the
only other request an ``ACK`` answers.  ``None`` where the program keeps no
such observations."""

from benchmarks.layers import inbox_wait_ms_per_frame


def read(ctx):
    return inbox_wait_ms_per_frame.ms_per_piece(ctx, "rpc.outbox.ACK")
