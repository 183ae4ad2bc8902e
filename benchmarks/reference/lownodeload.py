"""LowNodeLoad and migration arbitration, the plain way: sequential int64
numpy and Python lists, one pod and one job at a time.  Imports nothing of
the program.

Semantics (koord-descheduler's, as the program cites them in
``descheduler/lownodeload.py`` and ``migration.py``):

- a node's usage percent is ``usage * 100 // capacity`` per dimension (0
  where capacity is 0); a dimension with threshold -1 is not configured;
- a node is *under* when every configured dimension is below its low
  threshold, *over* when any is above its high threshold; with deviation
  thresholds low/high are the pool's mean percent -/+ the configured
  values, clamped to [0, 100];
- a node is *abnormal* once it has been over for ``anomaly_rounds``
  consecutive rounds, this one included;
- the pool's budget is, per configured dimension, the sum over under nodes
  of ``max(capacity * high // 100 - usage, 0)``;
- victims: the evictable pods of abnormal nodes, cheapest first (priority,
  then CPU usage, then position: the caller hands the pods over in the
  order of their names), one at a time, while the pod's node is
  still above its high quantity on some configured dimension and the
  budget covers the pod on every one; each victim's usage leaves its node
  and the budget;
- arbitration: pending jobs by (pod priority, creation); a job runs unless
  its node, its namespace or its workload already has its limit of
  migrating jobs, running ones included; a workload's limits scale with
  its replicas (``max_unavailable``), migrating pods count as unavailable.
"""

from __future__ import annotations

import numpy as np


def usage_percent(usage: np.ndarray, capacity: np.ndarray) -> np.ndarray:
    usage, capacity = usage.astype(np.int64), capacity.astype(np.int64)
    return np.where(capacity > 0, usage * 100 // np.maximum(capacity, 1), 0)


def thresholds(low, high, use_deviation: bool, pct: np.ndarray,
               valid: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    low, high = np.asarray(low, np.int64), np.asarray(high, np.int64)
    configured = low >= 0
    if use_deviation:
        mean = pct[valid].sum(axis=0) // max(int(valid.sum()), 1)
        low = np.clip(mean - np.maximum(low, 0), 0, 100)
        high = np.clip(mean + np.maximum(high, 0), 0, 100)
    return np.where(configured, low, -1), np.where(configured, high, -1)


def classify(pct, low, high, valid) -> tuple[np.ndarray, np.ndarray]:
    configured = low >= 0
    under = np.all((pct < low) | ~configured, axis=1) & valid
    over = np.any(configured & (pct > high), axis=1) & valid
    return under, over


class LowNodeLoad:
    """The plugin's state between rounds: the anomaly counters."""

    def __init__(self, low, high, use_deviation: bool, anomaly_rounds: int):
        self.low, self.high = low, high
        self.use_deviation = use_deviation
        self.anomaly_rounds = anomaly_rounds
        self.counters: np.ndarray | None = None

    def round(self, usage: np.ndarray, capacity: np.ndarray,
              valid: np.ndarray, pod_node: np.ndarray, pod_usage: np.ndarray,
              pod_priority: np.ndarray, evictable: np.ndarray
              ) -> tuple[list[int], np.ndarray]:
        """(victims as indices into the pod columns, in the order they
        were taken; the (N,) abnormal mask of this round)."""
        usage = usage.astype(np.int64)
        capacity = capacity.astype(np.int64)
        if self.counters is None:
            self.counters = np.zeros(len(usage), np.int64)
        pct = usage_percent(usage, capacity)
        low, high = thresholds(self.low, self.high, self.use_deviation, pct,
                               valid)
        under, over = classify(pct, low, high, valid)
        self.counters = np.where(over, self.counters + 1, 0)
        abnormal = over & (self.counters >= self.anomaly_rounds)
        configured = high >= 0
        high_quantity = capacity * np.maximum(high, 0) // 100
        room = np.maximum(high_quantity - usage, 0)
        budget = np.where(configured, room[under].sum(axis=0), 0)

        on_node = pod_node >= 0
        candidates = np.flatnonzero(
            evictable & on_node & abnormal[np.where(on_node, pod_node, 0)])
        order = sorted(candidates, key=lambda i: (int(pod_priority[i]),
                                                  int(pod_usage[i, 0]), i))
        node_usage = usage.copy()
        victims = []
        for i in order:
            node = pod_node[i]
            mine = pod_usage[i].astype(np.int64)
            if not np.any(configured
                          & (node_usage[node] > high_quantity[node])):
                continue
            if not np.all(~configured | (mine <= budget)):
                continue
            victims.append(int(i))
            node_usage[node] -= mine
            budget = budget - mine
        return victims, abnormal


def scaled_int_or_percent(spec, replicas: int) -> int:
    if isinstance(spec, str):
        return replicas * int(spec[:-1]) // 100
    return int(spec)


def max_unavailable(replicas: int, spec) -> int:
    """The replica rule: the spec scaled against replicas (a percent that
    floors to 0 still allows one); with no spec 10 % above 10 replicas, 2
    for 4-10, else 1; never more than the replicas."""
    most = 0
    if spec is not None:
        most = scaled_int_or_percent(spec, replicas) or 1
    if most == 0:
        most = (replicas * 10 // 100 if replicas > 10
                else 2 if replicas >= 4 else 1)
    return min(most, replicas)


def arbitrate(pending: list[dict], running: list[dict], limits: dict,
              replicas: dict[str, int]) -> list[str]:
    """Names of the pending jobs that may run, in the order they were let.
    A job is ``{name, node, namespace, workload, priority, created}``;
    ``limits`` holds ``per_node``, ``per_namespace``,
    ``migrating_per_workload`` and ``unavailable_per_workload`` (int,
    "N%" or None); ``replicas`` the expected replicas of each workload the
    controller finder knows."""
    node: dict[str, int] = {}
    namespace: dict[str, int] = {}
    workload: dict[str, int] = {}

    def count(job):
        node[job["node"]] = node.get(job["node"], 0) + 1
        namespace[job["namespace"]] = namespace.get(job["namespace"], 0) + 1
        if job["workload"]:
            workload[job["workload"]] = workload.get(job["workload"], 0) + 1

    for job in running:
        count(job)

    def flat(spec) -> int:
        return (spec if isinstance(spec, int) and not isinstance(spec, bool)
                and spec > 0 else 2)

    allowed = []
    for job in sorted(pending, key=lambda j: (j["priority"], j["created"])):
        if node.get(job["node"], 0) >= limits["per_node"]:
            continue
        if namespace.get(job["namespace"], 0) >= limits["per_namespace"]:
            continue
        ref = job["workload"]
        if ref:
            if replicas.get(ref):
                most_migrating = max_unavailable(
                    replicas[ref], limits["migrating_per_workload"])
                most_unavailable = max_unavailable(
                    replicas[ref], limits["unavailable_per_workload"])
            else:
                most_migrating = flat(limits["migrating_per_workload"])
                most_unavailable = flat(limits["unavailable_per_workload"])
            migrating = workload.get(ref, 0)
            if migrating >= most_migrating or migrating >= most_unavailable:
                continue
        allowed.append(job["name"])
        count(job)
    return allowed
