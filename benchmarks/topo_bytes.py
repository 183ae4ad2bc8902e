"""The least bytes one gang's topology plan must move, from shapes only.

Counted: what ANY implementation of the plan's device half has to touch.  It
reads every node's (L,) ancestor path once (int32) to sum the nodes' offer
slots up the tree, writes the (N,) offer slots and reads them back once, and
writes the (T,) slot aggregates of the tree once.  Not counted: the nodes'
free capacity and the members' requests (``solve_bytes`` counts a pod's
operands and a node's rows), the feasibility rows, the multiples' rounding
passes, the eligibility and ranking intermediates and the host walk.  So no
later kernel can push a share of the roofline built on this count past
100 %.
"""

from __future__ import annotations

INT32 = 4


def least_bytes(nodes: int, layers: int, topo_nodes: int) -> int:
    if min(nodes, layers, topo_nodes) < 1:
        raise ValueError("shapes must be positive")
    return INT32 * (nodes * layers + 2 * nodes + topo_nodes)
