"""The colocation loop, plainly: int64 numpy, one node after another where
order matters, nothing of the program imported.

Written from the published description of upstream koordinator (there is no
network here and no copy of the source: what follows is from memory of it,
and the configuration lists that under ``assumed``):

(a) ``pkg/slo-controller/noderesource/plugins/util/util.go``:
    ``CalculateBatchResourceByPolicy``, ``GetNodeSafetyMargin``,
    ``CalculateMidResourceByPolicy``: a node's batch and mid allocatable
    from its capacity and what its koordlet reported;
(b) ``plugins/batchresource/plugin.go`` ``isBatchResourceNeedSync``: a node
    is patched on its first sync, when its last patch is older than
    ``updateTimeThresholdSeconds``, or when a resource moved by more than
    ``resourceDiffThreshold``;
(c) the pod webhook under a ``ClusterColocationProfile``
    (``pkg/webhook/pod/mutating``: ``cluster_colocation_profile.go``,
    ``extended_resource_spec.go``): QoS, priority and scheduler name set,
    cpu and memory requests rewritten into ``kubernetes.io/batch-cpu``
    (milli-cores) and ``kubernetes.io/batch-memory`` (bytes);
(d) a replay of a window from a deployment's own books.

Departures from the published description, each because the system under
test states the same and the comparison is exact:

- memory is counted in MiB on the node side (the program's canonical unit;
  upstream counts bytes): capacity, reports and allocatable alike.  The
  webhook writes bytes, as upstream does;
- a percentage of a quantity is ``quantity * percent // 100`` in integers;
  upstream multiplies in float64 and truncates, which is the same number
  for quantities this small;
- ``batchCPUThresholdPercent`` / ``batchMemoryThresholdPercent`` (absent
  from the defaults: no cap) are left out;
- a resource "moved" when ``abs(new - old) / max(old, 1) > threshold`` in
  float64; upstream compares ``new`` with ``old * (1 +- threshold)``, the
  same rule but for a standing value of 0 (upstream: any change; here: a
  change of more than ``threshold`` units);
- the time-gap comparison is strict (``now - last > threshold``), as
  upstream's ``Clock.Since(last) > threshold``: with reports 60 s apart and
  the default 300 s a node at rest is patched every sixth interval;
- a node whose report is older than ``degradeTimeMinutes`` (or that never
  reported) is degraded: patched to zero once, then left until it reports;
  recovering counts as a first sync.
"""

from __future__ import annotations

import numpy as np

MIB = 1 << 20

#: ``slo-controller-config`` ``colocation-config`` defaults
DEFAULTS = {
    "enable": False,
    "metricAggregateDurationSeconds": 300,
    "metricReportIntervalSeconds": 60,
    "cpuReclaimThresholdPercent": 60,
    "memoryReclaimThresholdPercent": 65,
    "cpuCalculatePolicy": "usage",
    "memoryCalculatePolicy": "usage",
    "degradeTimeMinutes": 15,
    "updateTimeThresholdSeconds": 300,
    "resourceDiffThreshold": 0.1,
    "midCPUThresholdPercent": 10,
    "midMemoryThresholdPercent": 10,
    "midUnallocatedPercent": 0,
}

#: columns of a (N, 2) quantity: cpu in milli-cores, memory in MiB
CPU, MEM = 0, 1
#: columns of a (N, 4) allocatable
BATCH_CPU, BATCH_MEM, MID_CPU, MID_MEM = range(4)

LABEL_QOS = "koordinator.sh/qosClass"
RESOURCE_BATCH_CPU = "kubernetes.io/batch-cpu"
RESOURCE_BATCH_MEMORY = "kubernetes.io/batch-memory"
#: the koord-batch priority band
BATCH_PRIORITY = (5000, 5999)


# -- (a) the formula -----------------------------------------------------------

def _i64(a) -> np.ndarray:
    return np.asarray(a).astype(np.int64)


def safety_margin(capacity: np.ndarray, cfg: dict) -> np.ndarray:
    """(N, 2): capacity * (100 - reclaimThresholdPercent) / 100."""
    pct = np.array([100 - cfg["cpuReclaimThresholdPercent"],
                    100 - cfg["memoryReclaimThresholdPercent"]], np.int64)
    return _i64(capacity) * pct // 100


def batch_allocatable(capacity, sys_used, reserved, hp_used, hp_request,
                      hp_max_used_req, cfg: dict) -> np.ndarray:
    """(N, 2) batch cpu and memory by each dimension's calculate policy.
    CPU knows ``usage`` and ``maxUsageRequest``; memory ``request`` too."""
    capacity = _i64(capacity)
    base = capacity - safety_margin(capacity, cfg)
    sys_or_reserved = np.maximum(_i64(sys_used), _i64(reserved))
    by = {
        "usage": base - sys_or_reserved - _i64(hp_used),
        "request": base - _i64(reserved) - _i64(hp_request),
        "maxUsageRequest": base - sys_or_reserved - _i64(hp_max_used_req),
    }
    out = np.empty_like(capacity)
    for dim, policy in ((CPU, cfg["cpuCalculatePolicy"]),
                        (MEM, cfg["memoryCalculatePolicy"])):
        if policy not in by or (dim == CPU and policy == "request"):
            policy = "usage"
        out[:, dim] = np.maximum(by[policy][:, dim], 0)
    return out


def mid_allocatable(capacity, prod_reclaimable, node_used, hp_request,
                    cfg: dict) -> np.ndarray:
    """(N, 2): min(max(min(reclaimable, unused), 0) + unallocated *
    midUnallocatedPercent, capacity * midThresholdPercent)."""
    capacity = _i64(capacity)
    unused = np.maximum(capacity - _i64(node_used), 0)
    unallocated = np.maximum(capacity - _i64(hp_request), 0)
    mid = np.maximum(np.minimum(_i64(prod_reclaimable), unused), 0)
    mid = mid + unallocated * cfg["midUnallocatedPercent"] // 100
    cap = capacity * np.array([cfg["midCPUThresholdPercent"],
                               cfg["midMemoryThresholdPercent"]],
                              np.int64) // 100
    return np.minimum(mid, cap)


def allocatable(capacity: np.ndarray, report: dict, cfg: dict) -> np.ndarray:
    """(N, 4) batch cpu, batch memory, mid cpu, mid memory from a tick's
    reports: ``usage``, ``sys_usage``, ``hp_usage``, ``hp_request``,
    ``hp_max_used_req`` as (N, 2); ``reserved`` and ``prod_reclaimable``
    where a deployment has them (else 0)."""
    zero = np.zeros_like(_i64(capacity))
    batch = batch_allocatable(
        capacity, report["sys_usage"], report.get("reserved", zero),
        report["hp_usage"], report["hp_request"], report["hp_max_used_req"],
        cfg)
    mid = mid_allocatable(capacity, report.get("prod_reclaimable", zero),
                          report["usage"], report["hp_request"], cfg)
    return np.concatenate([batch, mid], axis=1)


# -- (b) the sync rule ---------------------------------------------------------

def sync_reason(last: np.ndarray | None, last_time: float, now: float,
                new: np.ndarray, cfg: dict) -> str | None:
    """One fresh node: why it is patched now, or None.  ``last`` is the
    (4,) allocatable of its last patch, None before the first."""
    if last is None:
        return "first"
    if now - last_time > cfg["updateTimeThresholdSeconds"]:
        return "time_gap"
    old, new = _i64(last), _i64(new)
    moved = (np.abs(new - old) / np.maximum(old, 1).astype(np.float64)
             > cfg["resourceDiffThreshold"])
    return "diff" if bool(np.any(moved & (old != new))) else None


class SyncState:
    """What the controller remembers of every node between ticks."""

    def __init__(self, nodes: int):
        self.last = np.full((nodes, 4), -1, np.int64)
        self.last_time = np.zeros(nodes, np.float64)
        self.synced = np.zeros(nodes, bool)
        self.degraded = np.zeros(nodes, bool)

    def step(self, now: float, values: np.ndarray,
             report_time: np.ndarray, cfg: dict
             ) -> tuple[np.ndarray, list[str | None]]:
        """One tick, node after node.  ``report_time``: when each node
        last reported, NaN for never.  Returns the (N,) mask of the nodes
        patched and, node by node, the reason.  ``self.last`` then holds
        what stands on every node."""
        stale_after = cfg["degradeTimeMinutes"] * 60
        patched = np.zeros(len(values), bool)
        reasons: list[str | None] = []
        for i in range(len(values)):
            seen = report_time[i]
            if np.isnan(seen) or now - seen > stale_after:
                reason = None if self.degraded[i] else "degraded"
                new = np.zeros(4, np.int64)
            else:
                first = not self.synced[i] or self.degraded[i]
                new = values[i]
                reason = sync_reason(None if first else self.last[i],
                                     self.last_time[i], now, new, cfg)
            reasons.append(reason)
            if reason is None:
                continue
            patched[i] = True
            self.last[i] = new
            self.last_time[i] = now
            self.synced[i] = True
            self.degraded[i] = reason == "degraded"
        return patched, reasons


# -- (c) admission -------------------------------------------------------------

_MEMORY_SUFFIX = {"Ki": 1 << 10, "Mi": 1 << 20, "Gi": 1 << 30, "Ti": 1 << 40,
                  "K": 10**3, "M": 10**6, "G": 10**9, "T": 10**12}


def cpu_milli(quantity) -> int:
    if isinstance(quantity, (int, float)):
        return int(quantity * 1000)
    text = str(quantity)
    return int(text[:-1]) if text.endswith("m") else int(float(text) * 1000)


def memory_bytes(quantity) -> int:
    if isinstance(quantity, (int, float)):
        return int(quantity)
    text = str(quantity)
    for suffix, unit in _MEMORY_SUFFIX.items():
        if text.endswith(suffix):
            return int(float(text[:-len(suffix)]) * unit)
    return int(float(text))


def admit(pod: dict, profile: dict) -> dict:
    """The pod as the mutating webhook must leave it under one
    ``ClusterColocationProfile`` (``pod_selector``, ``qos``, ``priority``,
    ``scheduler_name``).  A new dict; ``pod`` is read only."""
    meta = dict(pod.get("metadata", {}))
    labels = dict(meta.get("labels", {}))
    spec = dict(pod.get("spec", {}))
    if all(labels.get(k) == v for k, v in profile["pod_selector"].items()):
        labels[LABEL_QOS] = profile["qos"]
        spec["priority"] = profile["priority"]
        spec["schedulerName"] = profile["scheduler_name"]
    priority = spec.get("priority")
    batch = labels.get(LABEL_QOS) == "BE" and (
        priority is None
        or BATCH_PRIORITY[0] <= priority <= BATCH_PRIORITY[1])
    containers = []
    for container in spec.get("containers", []):
        container = dict(container)
        resources = {}
        for section, values in container.get("resources", {}).items():
            values = dict(values)
            if batch and section in ("requests", "limits"):
                if "cpu" in values:
                    values[RESOURCE_BATCH_CPU] = cpu_milli(values.pop("cpu"))
                if "memory" in values:
                    values[RESOURCE_BATCH_MEMORY] = memory_bytes(
                        values.pop("memory"))
            resources[section] = values
        container["resources"] = resources
        containers.append(container)
    if "containers" in spec:
        spec["containers"] = containers
    meta["labels"] = labels
    if "annotations" in pod.get("metadata", {}):
        meta["annotations"] = dict(pod["metadata"]["annotations"])
    return dict(pod, metadata=meta, spec=spec)


def request_vector(pod: dict, dims: dict) -> np.ndarray:
    """(R,) int32: what the scheduler is told an admitted pod asks for.
    Batch resources land on the batch dimensions (memory in MiB), plain
    cpu and memory on theirs."""
    out = np.zeros(dims["count"], np.int64)
    for container in pod["spec"].get("containers", []):
        requests = container.get("resources", {}).get("requests", {})
        if "cpu" in requests:
            out[dims["cpu"]] += cpu_milli(requests["cpu"])
        if "memory" in requests:
            out[dims["memory"]] += memory_bytes(requests["memory"]) // MIB
        out[dims["batch_cpu"]] += int(requests.get(RESOURCE_BATCH_CPU, 0))
        out[dims["batch_memory"]] += (
            int(requests.get(RESOURCE_BATCH_MEMORY, 0)) // MIB)
    return out.astype(np.int32)


# -- (d) a window, replayed ----------------------------------------------------

def replay_ticks(capacity: np.ndarray, ticks: list[dict], cfg: dict
                 ) -> list[dict]:
    """Every tick of a run from its first: ``ticks[k]`` holds ``now``,
    ``report_time`` (N,) and the reports of ``allocatable``.  Per tick:
    ``patched`` (N,) mask, ``standing`` (N, 4): what stands on every node
    after it (-1 where nothing was ever patched), ``reasons``."""
    state = SyncState(len(capacity))
    out = []
    for tick in ticks:
        values = allocatable(capacity, tick, cfg)
        patched, reasons = state.step(tick["now"], values,
                                      tick["report_time"], cfg)
        out.append({"patched": patched, "standing": state.last.copy(),
                    "reasons": reasons})
    return out


def bind_violations(rounds: list[dict]) -> tuple[int, int, int]:
    """``rounds[k]``: ``batch`` (N, 2) batch allocatable standing at the
    round, ``requested`` (N, 2) BE requests bound on every node going in,
    ``rows`` (K,) and ``requests`` (K, 2): the round's BE binds in the
    order they were answered.  Returns (binds that took a node over its
    batch allocatable or found it over, binds onto a node that was
    squeezed going in, nodes squeezed going into the last round)."""
    over = on_squeezed = squeezed_nodes = 0
    for entry in rounds:
        batch = _i64(entry["batch"])
        requested = _i64(entry["requested"]).copy()
        squeezed = np.any(requested > batch, axis=1)
        squeezed_nodes = int(squeezed.sum())
        for row, request in zip(entry["rows"], _i64(entry["requests"])):
            requested[row] += request
            over += bool(np.any(requested[row] > batch[row]))
            on_squeezed += bool(squeezed[row])
    return over, on_squeezed, squeezed_nodes
