"""The least bytes one round of LowNodeLoad victim selection must move,
from shapes only.

Counted: what ANY implementation has to touch.  It reads, per candidate pod
(a pod of a node that is over its high threshold and past the anomaly
gate), its usage vector, node row, priority and evictable flag (R + 3
int32), and per node its usage and allocatable (2 R int32); it writes one
flag per candidate.  Not counted: the anomaly counters, thresholds,
budgets, sort keys and the per-step carry an implementation keeps, nor the
padding of its bucket.  So no later kernel can push a share of the roofline
built on this count past 100 %.
"""

from __future__ import annotations

INT32 = 4


def least_bytes(candidates: int, nodes: int, r: int) -> int:
    if candidates < 0 or min(nodes, r) < 1:
        raise ValueError("shapes must be positive")
    read = (candidates * (r + 3) + nodes * 2 * r) * INT32
    return read + candidates * INT32
