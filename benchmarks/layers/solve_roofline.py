"""solve kernels: the least time the chip's memory system could take for
the round's least bytes (``solve_bytes.least_bytes``: shapes only, no
intermediates), over the device time the solve took.  Bandwidth-bound by
shape: the solve is integer compare-and-select work with no matrix product.
"""

from benchmarks import solve_bytes


def read(ctx):
    if ctx.trace is None or not ctx.rounds or ctx.trace.solve_busy_s <= 0:
        return None
    s = ctx.shapes
    least = sum(solve_bytes.least_bytes(solve_bytes.pad_pow2(r["pods"]),
                                        s["nodes"], s["dims"], s["passes"])
                for r in ctx.rounds if r["pods"] > 0)
    floor_s = least / ctx.peak["hbm_bytes_per_s"]
    return 100.0 * floor_s / ctx.trace.solve_busy_s
