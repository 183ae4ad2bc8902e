"""colocation kernels: the least time the chip's memory system could take
for the least bytes of the window's reconciles
(``colocation_bytes.least_bytes``: shapes only), over the device time inside
the ``colo_tick`` spans.  The formula is a dozen element-wise integer ops
over a few (N,) columns: the share says how far one tiny dispatch sits from
the memory system."""

from benchmarks import colocation_bytes
from benchmarks.layers import colo_reconcile_device_ms


def read(ctx):
    busy, ticks = colo_reconcile_device_ms.busy_and_ticks(ctx)
    if not ticks or busy <= 0:
        return None
    least = ticks * colocation_bytes.least_bytes(ctx.shapes["nodes"])
    return 100.0 * least / ctx.peak["hbm_bytes_per_s"] / busy
