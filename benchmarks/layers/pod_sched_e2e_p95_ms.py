"""pending queue: p95 over every pod bound by a round committed inside the
window of the time from its enqueue to the bind's acknowledgement, all
inside the scheduler (the journey ledger's stage ``e2e``: queue wait, the
round up to the commit, the commit's bookkeeping).  The client's
``pod_e2e_p95_ms`` less this is the ``pod_add`` frame, the solve response
and its decode.  ``None`` as ``pod_queue_wait_p95_ms``."""

from benchmarks.layers import pod_queue_wait_p95_ms


def read(ctx):
    return pod_queue_wait_p95_ms.stage_quantile_ms(ctx, "e2e", 0.95)
