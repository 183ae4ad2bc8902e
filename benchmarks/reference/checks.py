"""The guarantees, recomputed in numpy int64 from the books a run keeps.

Copied from ``chip_smoke.py``'s ``check_no_overcommit`` / ``check_gangs`` /
``check_quotas`` and changed in one way: each check returns the NUMBER of
violations instead of raising on the first, because the harness prints every
number it compares beside its limit (all limits here are 0: the comparisons
are exact).
"""

from __future__ import annotations

import numpy as np


def requested_by_node(n_nodes: int, node_row: dict[str, int],
                      requests: dict[str, np.ndarray],
                      bound: dict[str, str], dims: int
                      ) -> tuple[np.ndarray, int]:
    """((n_nodes, R) int64 requested summed from the assignments alone,
    number of pods bound to a node that was never sent)."""
    known = [(p, node_row[n]) for p, n in bound.items() if n in node_row]
    requested = np.zeros((n_nodes, dims), np.int64)
    if known:
        rows = np.fromiter((r for _, r in known), np.int64, len(known))
        req = np.stack([requests[p] for p, _ in known]).astype(np.int64)
        np.add.at(requested, rows, req)
    return requested, len(bound) - len(known)


def overcommit_cells(alloc: np.ndarray, requested: np.ndarray) -> int:
    """(node, dim) cells whose summed requests exceed allocatable."""
    return int(np.count_nonzero(requested > alloc.astype(np.int64)))


def mismatch_cells(held: np.ndarray, expected: np.ndarray) -> int:
    """(row, dim) cells where what the scheduler holds differs from what
    the books say it must hold."""
    return int(np.count_nonzero(
        np.asarray(held).astype(np.int64) != expected.astype(np.int64)))


def partial_gangs(members: dict[str, list[str]], min_member: int,
                  bound: dict[str, str]) -> tuple[int, int]:
    """(gangs bound in part, gangs bound whole)."""
    partial = whole = 0
    for pods in members.values():
        n = sum(1 for p in pods if p in bound)
        whole += n >= min_member
        partial += 0 < n < min_member
    return partial, whole


def quota_over(pod_quota: dict[str, str], requests: dict[str, np.ndarray],
               bound: dict[str, str], limits: dict[str, np.ndarray]) -> int:
    """Quotas whose used, summed in int64 from the assignments, exceeds the
    limit on a dim the limit bounds (a negative limit is unbounded)."""
    used = {q: np.zeros(lim.shape, np.int64) for q, lim in limits.items()}
    for pod in bound:
        quota = pod_quota.get(pod)
        if quota is not None:
            used[quota] += requests[pod].astype(np.int64)
    over = 0
    for quota, lim in limits.items():
        bounded = lim >= 0
        over += bool(np.any(used[quota][bounded] > lim[bounded]))
    return over
