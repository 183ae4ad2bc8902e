"""Cluster and pod data, made in bulk from a numpy generator.

Copied from ``chip_smoke.py`` (PR 21) so that a later PR can change the
program without changing the yardstick; the only edit is that resource
dimension indices and QoS codes come from the configuration file instead of
the program's enums (``run.py`` refuses to start if the two disagree).

Value ranges are ``__graft_entry__._build_problem``'s: node CPU 8-64 cores,
memory 16-256 GiB, batch dims = half of allocatable (standing in for the
manager's colocation output), usage up to 50 %; pod 0.1-4 cores,
128 MiB-8 GiB, a quarter BE on batch resources in the batch priority band,
the rest LS split over the prod and mid bands.
"""

from __future__ import annotations

import numpy as np


def make_nodes(rng, n: int, dims: dict) -> tuple[np.ndarray, np.ndarray]:
    """(alloc, usage), each (n, R) int32."""
    alloc = np.zeros((n, dims["count"]), np.int32)
    alloc[:, dims["cpu"]] = rng.integers(8_000, 64_000, n)
    alloc[:, dims["memory"]] = rng.integers(16_384, 262_144, n)
    alloc[:, dims["batch_cpu"]] = alloc[:, dims["cpu"]] // 2
    alloc[:, dims["batch_memory"]] = alloc[:, dims["memory"]] // 2
    return alloc, make_usage(rng, alloc)


def make_usage(rng, alloc: np.ndarray) -> np.ndarray:
    return (alloc * rng.random(alloc.shape) * 0.5).astype(np.int32)


def make_pods(rng, n: int, dims: dict,
              qos: dict) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(requests (n, R) int32, priority (n,), qos (n,))."""
    cpu = rng.integers(100, 4_000, n)
    mem = rng.integers(128, 8_192, n)
    be = rng.random(n) < 0.25
    req = np.zeros((n, dims["count"]), np.int32)
    req[:, dims["cpu"]] = np.where(be, 0, cpu)
    req[:, dims["memory"]] = np.where(be, 0, mem)
    req[:, dims["batch_cpu"]] = np.where(be, cpu, 0)
    req[:, dims["batch_memory"]] = np.where(be, mem, 0)
    prod = rng.random(n) < 0.5
    prio = np.where(be, rng.integers(5_000, 6_000, n),
                    np.where(prod, rng.integers(9_000, 10_000, n),
                             rng.integers(7_000, 8_000, n)))
    qos_col = np.where(be, qos["BE"], qos["LS"])
    return req, prio.astype(np.int64), qos_col.astype(np.int64)


def whale_request(dims: dict) -> np.ndarray:
    """More CPU than any node has: fits nowhere, must carry a diagnosis."""
    req = np.zeros(dims["count"], np.int32)
    req[dims["cpu"]] = 100_000
    req[dims["memory"]] = 1_024
    return req
