"""The control: the reference put in the program's place with one guarantee
broken.  It must come out as not correct, or the comparison proves nothing.

The guarantee broken is the one a later PR would be tempted by: the accept
step.  Every pending pod proposes to the best of ``k`` random candidate
nodes by free room (all dimensions summed) at the start of the round, among those it fits on, and
every proposal is accepted with no look at what the other pods of the round
proposed.  Pods of one round pile onto the roomiest nodes, so nodes end up
over allocatable: ``overcommit_cells`` must read above 0.
"""

from __future__ import annotations

import numpy as np


def propose_without_accept(rng, alloc: np.ndarray, requested: np.ndarray,
                           requests: np.ndarray, k: int = 256) -> np.ndarray:
    """(P,) node row per pod, -1 where no candidate fits at round start."""
    alloc = alloc.astype(np.int64)
    free = alloc - requested
    room = free.sum(axis=1).astype(np.float64)
    cands = rng.integers(0, alloc.shape[0], (len(requests), k))
    fits = np.ones(cands.shape, bool)
    for dim in range(alloc.shape[1]):       # dim by dim: (P, k, R) is large
        fits &= requests[:, dim, None] <= free[:, dim][cands]
    score = np.where(fits, room[cands], -1.0)
    best = score.argmax(axis=1)
    rows = cands[np.arange(len(requests)), best]
    return np.where(score.max(axis=1) >= 0, rows, -1)
