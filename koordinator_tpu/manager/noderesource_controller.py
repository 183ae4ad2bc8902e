"""NodeResource controller: the colocation loop's central math (reference:
``pkg/slo-controller/noderesource/noderesource_controller.go:71`` Reconcile +
the plugin framework ``framework/extender_plugin.go`` with
ResourceCalculate / NodePrepare / NodeSync stages).

TPU-native redesign: the reference reconciles one node per event; here one
tick batches EVERY node's formula into a single jitted tensor call over
(N,)-vectors (manager/noderesource.py kernels), then per-node host logic
(degrade, diff-threshold sync suppression, device sync) consumes the result.

Units: cpu milli-cores, memory MiB (resources.py convention; NodeMetric
reports bytes and is converted on ingestion).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Mapping, Optional

import jax
import jax.numpy as jnp
import numpy as np

from koordinator_tpu.api import crds, extension as ext
from koordinator_tpu.manager import noderesource as formula
from koordinator_tpu.manager.sloconfig import ColocationConfig

MIB = 1 << 20


@dataclasses.dataclass
class NodeRecord:
    """Everything the controller knows about one node."""

    name: str
    cpu_capacity_milli: int
    mem_capacity_mib: int
    labels: Mapping[str, str] = dataclasses.field(default_factory=dict)
    annotations: Mapping[str, str] = dataclasses.field(default_factory=dict)
    metric: Optional[crds.NodeMetricStatus] = None
    device: Optional[crds.Device] = None
    #: sums over Prod+Mid pods on the node (from the pod informer)
    hp_request_cpu_milli: int = 0
    hp_request_mem_mib: int = 0
    #: per-pod max(request, usage) summed (for the maxUsageRequest policy)
    hp_max_used_req_cpu_milli: int = 0
    hp_max_used_req_mem_mib: int = 0
    #: prod reclaimable from the usage forecaster (mid-resource input)
    prod_reclaimable_cpu_milli: int = 0
    prod_reclaimable_mem_mib: int = 0
    #: pre-aggregated HP (Prod+Mid) usage — set when the record comes
    #: from the wire (the koordlet's node_usage hp_usage array) instead
    #: of a full NodeMetric with per-pod rows; overrides the
    #: pods_metrics sum when not None
    hp_used_cpu_milli: Optional[int] = None
    hp_used_mem_mib: Optional[int] = None
    #: last synced values (for diff-threshold / no-op patch suppression)
    last_batch_cpu: int = -1
    last_batch_mem: int = -1
    last_mid_cpu: int = -1
    last_mid_mem: int = -1
    last_device_resources: Optional[Mapping[str, int]] = None
    last_degraded: bool = False
    #: controller clock at the last sync (the time-gap rule's input)
    last_sync_time: float = 0.0


@dataclasses.dataclass(frozen=True)
class NodePatch:
    """The NodeSync output: extended resources to patch onto node status."""

    name: str
    batch_cpu_milli: int
    batch_mem_mib: int
    mid_cpu_milli: int
    mid_mem_mib: int
    device_resources: Mapping[str, int] = dataclasses.field(default_factory=dict)
    degraded: bool = False


def _policy_code(policy: str) -> int:
    return {
        "usage": formula.POLICY_USAGE,
        "request": formula.POLICY_REQUEST,
        "maxUsageRequest": formula.POLICY_MAX_USAGE_REQUEST,
    }.get(policy, formula.POLICY_USAGE)


class NodeResourceController:
    def __init__(self, config: Optional[ColocationConfig] = None,
                 clock=time.time):
        self.config = config or ColocationConfig(enable=True)
        self.clock = clock
        self._batched = jax.jit(self._compute_batched)

    # ---- the batched tensor stage ------------------------------------------

    @staticmethod
    def _compute_batched(inputs: dict, strategy: formula.ColocationStrategy):
        batch_cpu, batch_mem = formula.batch_allocatable(
            inputs["cap_cpu"], inputs["cap_mem"],
            inputs["sys_used_cpu"], inputs["sys_used_mem"],
            inputs["reserved_cpu"], inputs["reserved_mem"],
            inputs["hp_used_cpu"], inputs["hp_used_mem"],
            inputs["hp_req_cpu"], inputs["hp_req_mem"],
            inputs["hp_max_cpu"], inputs["hp_max_mem"],
            strategy,
        )
        unallocated_cpu = jnp.maximum(
            inputs["cap_cpu"] - inputs["hp_req_cpu"], 0
        )
        unallocated_mem = jnp.maximum(
            inputs["cap_mem"] - inputs["hp_req_mem"], 0
        )
        node_unused_cpu = jnp.maximum(inputs["cap_cpu"] - inputs["node_used_cpu"], 0)
        node_unused_mem = jnp.maximum(inputs["cap_mem"] - inputs["node_used_mem"], 0)
        mid_cpu, mid_mem = formula.mid_allocatable(
            inputs["cap_cpu"], inputs["cap_mem"],
            inputs["reclaim_cpu"], inputs["reclaim_mem"],
            node_unused_cpu, node_unused_mem,
            unallocated_cpu, unallocated_mem,
            strategy,
        )
        return batch_cpu, batch_mem, mid_cpu, mid_mem

    def _strategy(self) -> formula.ColocationStrategy:
        c = self.config
        i32 = jnp.int32
        return formula.ColocationStrategy(
            cpu_reclaim_threshold_pct=i32(c.cpu_reclaim_threshold_percent),
            memory_reclaim_threshold_pct=i32(c.memory_reclaim_threshold_percent),
            cpu_calculate_policy=i32(_policy_code(c.cpu_calculate_policy)),
            memory_calculate_policy=i32(_policy_code(c.memory_calculate_policy)),
            batch_cpu_threshold_pct=i32(100),
            batch_memory_threshold_pct=i32(100),
            mid_cpu_threshold_pct=i32(c.mid_cpu_threshold_percent),
            mid_memory_threshold_pct=i32(c.mid_memory_threshold_percent),
            mid_unallocated_pct=i32(c.mid_unallocated_percent),
        )

    # ---- reconcile ----------------------------------------------------------

    def reconcile(self, nodes: list[NodeRecord]) -> list[NodePatch]:
        """One controller tick over every node. Returns patches for nodes
        whose batch/mid resources changed beyond the diff threshold (plus all
        degraded nodes)."""
        if not nodes:
            return []
        from koordinator_tpu import timeline

        with timeline.RECORDER.section("host_other", "colo.reconcile",
                                       n=len(nodes)):
            return self._reconcile(nodes, timeline.RECORDER)

    def _reconcile(self, nodes: list[NodeRecord], tl) -> list[NodePatch]:
        now = self.clock()

        def col(fn) -> np.ndarray:
            return np.asarray([fn(r) for r in nodes], np.int32)

        def metric_or(r: NodeRecord, fn, default=0) -> int:
            return fn(r.metric) if r.metric is not None else default

        # CPU normalization + amplification prepare stage (annotations).
        cap_cpu_raw = col(lambda r: r.cpu_capacity_milli)
        norm_pct = col(
            lambda r: ext.get_cpu_normalization_ratio_pct(r.annotations)
        )
        amp = [ext.get_node_amplification_ratios(r.annotations) for r in nodes]
        amp_cpu_pct = np.asarray(
            [a.get("cpu", 100) for a in amp], np.int32
        )
        cap_cpu = np.asarray(
            formula.cpu_normalization(jnp.asarray(cap_cpu_raw), jnp.asarray(norm_pct))
        )
        cap_cpu = np.asarray(
            formula.amplify_capacity(jnp.asarray(cap_cpu), jnp.asarray(amp_cpu_pct))
        )

        inputs = {
            "cap_cpu": jnp.asarray(cap_cpu),
            "cap_mem": jnp.asarray(col(lambda r: r.mem_capacity_mib)),
            "sys_used_cpu": jnp.asarray(col(
                lambda r: metric_or(r, lambda m: m.system_usage.cpu_milli))),
            "sys_used_mem": jnp.asarray(col(
                lambda r: metric_or(r, lambda m: m.system_usage.memory_bytes // MIB))),
            "reserved_cpu": jnp.asarray(col(
                lambda r: int(ext.get_node_reservation(r.annotations).get("cpu", 0)))),
            "reserved_mem": jnp.asarray(col(
                lambda r: int(ext.get_node_reservation(r.annotations).get("memory", 0)))),
            "hp_used_cpu": jnp.asarray(col(lambda r: self._hp_used_cpu(r))),
            "hp_used_mem": jnp.asarray(col(lambda r: self._hp_used_mem(r))),
            "hp_req_cpu": jnp.asarray(col(lambda r: r.hp_request_cpu_milli)),
            "hp_req_mem": jnp.asarray(col(lambda r: r.hp_request_mem_mib)),
            "hp_max_cpu": jnp.asarray(col(lambda r: r.hp_max_used_req_cpu_milli)),
            "hp_max_mem": jnp.asarray(col(lambda r: r.hp_max_used_req_mem_mib)),
            "node_used_cpu": jnp.asarray(col(
                lambda r: metric_or(r, lambda m: m.node_usage.cpu_milli))),
            "node_used_mem": jnp.asarray(col(
                lambda r: metric_or(r, lambda m: m.node_usage.memory_bytes // MIB))),
            "reclaim_cpu": jnp.asarray(col(lambda r: r.prod_reclaimable_cpu_milli)),
            "reclaim_mem": jnp.asarray(col(lambda r: r.prod_reclaimable_mem_mib)),
        }
        with tl.section("host_other", "colo.solve"):
            batch_cpu, batch_mem, mid_cpu, mid_mem = map(
                np.asarray, self._batched(inputs, self._strategy())
            )

        from koordinator_tpu import metrics

        patches: list[NodePatch] = []
        for i, record in enumerate(nodes):
            degraded = self._degraded(record, now)
            b_cpu = 0 if degraded else int(batch_cpu[i])
            b_mem = 0 if degraded else int(batch_mem[i])
            m_cpu = 0 if degraded else int(mid_cpu[i])
            m_mem = 0 if degraded else int(mid_mem[i])
            devres = self._device_resources(record)
            # observability: every tick refreshes the gauges, even for nodes
            # below the diff threshold that emit no patch
            metrics.batch_resource_allocatable.set(
                float(b_cpu), labels={"node": record.name,
                                      "resource": "batch-cpu"})
            metrics.batch_resource_allocatable.set(
                float(b_mem), labels={"node": record.name,
                                      "resource": "batch-memory"})
            metrics.node_metric_expired.set(
                1.0 if degraded else 0.0, labels={"node": record.name})
            if degraded:
                # already zeroed — but device info comes from the Device CR,
                # independent of metric freshness, so device changes still sync
                if (record.last_degraded
                        and record.last_device_resources == devres):
                    continue
                reason = "degraded"
            else:
                reason = self._sync_reason(
                    record, now, b_cpu, b_mem, m_cpu, m_mem, devres)
                if reason is None:
                    continue
            metrics.colocation_sync_reason_total.inc(
                labels={"reason": reason})
            record.last_batch_cpu, record.last_batch_mem = b_cpu, b_mem
            record.last_mid_cpu, record.last_mid_mem = m_cpu, m_mem
            record.last_device_resources = dict(devres)
            record.last_degraded = degraded
            record.last_sync_time = now
            patches.append(NodePatch(
                name=record.name,
                batch_cpu_milli=b_cpu, batch_mem_mib=b_mem,
                mid_cpu_milli=m_cpu, mid_mem_mib=m_mem,
                device_resources=devres,
                degraded=degraded,
            ))
        return patches

    # ---- helper stages ------------------------------------------------------

    def _hp_used_cpu(self, record: NodeRecord) -> int:
        from koordinator_tpu.api.priority import is_hp_band

        if record.hp_used_cpu_milli is not None:
            return record.hp_used_cpu_milli
        if record.metric is None:
            return 0
        return sum(
            p.usage.cpu_milli for p in record.metric.pods_metrics
            if is_hp_band(p.qos_class, p.priority)
        )

    def _hp_used_mem(self, record: NodeRecord) -> int:
        from koordinator_tpu.api.priority import is_hp_band

        if record.hp_used_mem_mib is not None:
            return record.hp_used_mem_mib
        if record.metric is None:
            return 0
        return sum(
            p.usage.memory_bytes // MIB for p in record.metric.pods_metrics
            if is_hp_band(p.qos_class, p.priority)
        )

    def _degraded(self, record: NodeRecord, now: float) -> bool:
        """NodeMetric stale beyond degradeTimeMinutes -> zero out colocation
        resources (the reference's degrade mode)."""
        if record.metric is None:
            return True
        if getattr(record.metric, "degraded", False):
            return True  # koordlet reported collectors-silent explicitly
        age = now - record.metric.update_time
        return age > self.config.degrade_time_minutes * 60

    def _sync_reason(self, record: NodeRecord, now: float, b_cpu: int,
                     b_mem: int, m_cpu: int, m_mem: int,
                     devres: Mapping[str, int]) -> Optional[str]:
        """Why a fresh node is patched this tick, or None when it is not
        (plugins/batchresource/plugin.go isBatchResourceNeedSync): its
        ``first`` sync (also a node recovering from degrade), a
        ``time_gap`` (the last sync is older than
        updateTimeThresholdSeconds), or a ``diff``: the relative change of
        a dimension is above resourceDiffThreshold, or the device
        resources changed."""
        if record.last_batch_cpu < 0 or record.last_degraded:
            return "first"
        if (now - record.last_sync_time
                > self.config.update_time_threshold_seconds):
            return "time_gap"
        if record.last_device_resources != devres:
            return "diff"
        threshold = self.config.resource_diff_threshold

        def differs(old: int, new: int) -> bool:
            if old == new:
                return False
            base = max(old, 1)
            return abs(new - old) / base > threshold

        if (differs(record.last_batch_cpu, b_cpu)
                or differs(record.last_batch_mem, b_mem)
                or differs(record.last_mid_cpu, m_cpu)
                or differs(record.last_mid_mem, m_mem)):
            return "diff"
        return None

    def _device_resources(self, record: NodeRecord) -> dict[str, int]:
        """gpudeviceresource/rdmadevicereource NodeSync: Device CR ->
        node-level extended resources."""
        if record.device is None:
            return {}
        out: dict[str, int] = {}
        for dev in record.device.devices:
            if not dev.health:
                continue
            if dev.type == "gpu":
                out[ext.RESOURCE_GPU] = out.get(ext.RESOURCE_GPU, 0) + 100
                out[ext.RESOURCE_GPU_CORE] = out.get(ext.RESOURCE_GPU_CORE, 0) + 100
                mem = dev.resources.get(ext.RESOURCE_GPU_MEMORY, 0)
                out[ext.RESOURCE_GPU_MEMORY] = (
                    out.get(ext.RESOURCE_GPU_MEMORY, 0) + mem
                )
            elif dev.type == "rdma":
                out[ext.RESOURCE_RDMA] = out.get(ext.RESOURCE_RDMA, 0) + 100
            else:
                # xpu / tpu / vendor devices: publish their declared resource
                # quantities as-is (xpudeviceresource parity)
                for res, amount in dev.resources.items():
                    out[res] = out.get(res, 0) + int(amount)
        return out
