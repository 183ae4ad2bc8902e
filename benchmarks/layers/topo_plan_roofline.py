"""topology kernels: the least time the chip's memory system could take for
the least bytes of the window's gang plans (``topo_bytes.least_bytes`` a
gang: the (N, L) ancestor paths, the (N,) offer slots and the (T,)
aggregates, from the tree's shape each ``solve_request`` span carries), over
the device time inside the program's ``topo.plan`` spans.

The denominator holds every device op inside those spans: the prep program
of each gang, the charge, and the transfers of a plan's rows to the host.
So it is a floor of the prep kernel's own share; read it beside
``topo_plan_device_ms``."""

from benchmarks import topo_bytes
from benchmarks.layers import topo_plan_device_ms


def read(ctx):
    busy, spans = topo_plan_device_ms.busy_and_spans(ctx)
    if not spans or busy <= 0:
        return None
    shapes = [c.get("topo_tree") for *_, c in ctx._window("solve_request")]
    if not shapes or shapes[0] is None:
        return None
    nodes, layers, topo_nodes = shapes[0]
    gangs = sum(n for _, _, n in spans)
    least = gangs * topo_bytes.least_bytes(nodes, layers, topo_nodes)
    return 100.0 * least / ctx.peak["hbm_bytes_per_s"] / busy
