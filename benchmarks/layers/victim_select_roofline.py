"""descheduler kernels: the least time the chip's memory system could take
for the least bytes of the window's victim selections
(``victim_bytes.least_bytes``: shapes only; the candidates are the members
of the program's ``desched.select`` spans), over the device time inside the
``desched_balance`` spans.  The walk is one pod at a time by its semantics,
so the share says how far a sequential scan sits from the memory system."""

from benchmarks import program_spans, victim_bytes
from benchmarks.layers import victim_select_device_ms


def read(ctx):
    busy, rounds = victim_select_device_ms.busy_and_rounds(ctx)
    _, candidates = program_spans.total(
        program_spans.records(ctx), lambda r: r["name"] == "desched.select")
    if not rounds or busy <= 0:
        return None
    s = ctx.shapes
    least = sum(victim_bytes.least_bytes(int(candidates / rounds),
                                         s["nodes"], s["dims"])
                for _ in range(rounds))
    return 100.0 * least / ctx.peak["hbm_bytes_per_s"] / busy
