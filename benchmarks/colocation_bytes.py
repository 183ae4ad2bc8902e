"""The least bytes one noderesource reconcile must move, from shapes only.

Counted: what ANY implementation has to touch.  Per node it reads the
columns the formula cannot do without under any policy (capacity, system
usage, node reservation, prod and mid usage, prod and mid requests, the sum
of per-pod max(request, usage), node usage and prod reclaimable: 9 columns
of cpu and memory each, int32) and writes the four it computes (batch cpu,
batch memory, mid cpu, mid memory).  Not counted: the safety margin, the
unused and unallocated intermediates, the strategy's scalars, or any
padding.  So no later kernel can push a share of the roofline built on this
count past 100 %.
"""

from __future__ import annotations

INT32 = 4
#: input quantities, each a cpu and a memory column
READ_COLUMNS = 9 * 2
WRITTEN_COLUMNS = 4


def least_bytes(nodes: int) -> int:
    if nodes < 1:
        raise ValueError("shapes must be positive")
    return nodes * (READ_COLUMNS + WRITTEN_COLUMNS) * INT32
