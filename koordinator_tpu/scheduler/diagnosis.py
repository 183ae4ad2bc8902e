"""Schedule diagnosis: structured "why unschedulable" explanations.

Equivalent of ``frameworkext/schedule_diagnosis.go:44-108`` — when a pod fails
to place, report how many nodes each filter stage eliminated, so operators see
"0/128 nodes available: 96 insufficient cpu, 30 usage over threshold, 2
affinity mismatch" instead of a bare failure.

The stage masks are recomputed per failed pod (failures are rare relative to
the hot path, and the per-stage breakdown is exactly what score_pods fuses
away for speed).
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from koordinator_tpu.ops import deviceshare, filtering, scoring
from koordinator_tpu.ops.assignment import ScoringConfig
from koordinator_tpu.state.cluster_state import ClusterState, PodBatch


@dataclasses.dataclass
class PodDiagnosis:
    """Counts of nodes eliminated per stage (a node counts once, first-fail)."""

    total_nodes: int
    feasible_nodes: int
    insufficient_resources: int
    usage_over_threshold: int
    affinity_mismatch: int
    quota_rejected: bool
    invalid: int
    #: PostFilter outcome: nominated node + victims when preemption helps
    #: (schedule_diagnosis.go records the same on the explanation)
    preempt_node: str | None = None
    preempt_victims: list[str] = dataclasses.field(default_factory=list)
    #: fine-grained reject-reason counts keyed by ops/explain.REASON_NAMES
    #: (per-dim fit, threshold, affinity, plus host-filled pod-level
    #: gates); None when the explain accounting was disabled
    reason_counts: dict[str, int] | None = None
    #: nodes that passed every other filter and on which no device (or
    #: too few whole ones) fits the pod's device request (DeviceShare)
    device_unfit: int = 0

    def message(self) -> str:
        msg = self._base_message()
        if self.preempt_node is not None:
            victims = ", ".join(self.preempt_victims)
            msg += (f"; fits on {self.preempt_node} after preempting "
                    f"[{victims}]")
        return msg

    def _base_message(self) -> str:
        if self.quota_rejected:
            return "pod rejected by elastic quota admission"
        parts = []
        if self.insufficient_resources:
            part = f"{self.insufficient_resources} insufficient resources"
            # of them, the nodes short on the aggregate gpu rows: a device
            # pod's diagnosis names the dimension it is waiting for
            gpu = sum((self.reason_counts or {}).get(name, 0)
                      for name in ("fit_gpu", "fit_gpu_memory"))
            parts.append(f"{part} ({gpu} gpu)" if gpu else part)
        if self.usage_over_threshold:
            parts.append(f"{self.usage_over_threshold} usage over threshold")
        if self.affinity_mismatch:
            parts.append(f"{self.affinity_mismatch} didn't match node selector")
        if self.device_unfit:
            parts.append(f"{self.device_unfit} insufficient devices (gpu)")
        detail = ", ".join(parts) if parts else "no failure recorded"
        return (f"{self.feasible_nodes}/{self.total_nodes} nodes available: "
                f"{detail}")


def explain_pod(
    state: ClusterState,
    pods: PodBatch,
    cfg: ScoringConfig,
    pod_idx: int,
    quota_admitted: bool = True,
) -> PodDiagnosis:
    """Stage-by-stage elimination breakdown for one pod of the batch."""
    req = pods.requests[pod_idx][None, :]
    pod_est = scoring.estimate_pod_usage_by_band(
        req, cfg.estimator_factors, cfg.estimator_defaults
    )
    valid = np.asarray(state.node_valid)
    total = int(valid.sum())

    fit = np.asarray(filtering.fit_mask(state.free, req)[0]) & valid
    inst = filtering.usage_threshold_mask(
        state.node_usage, state.node_allocatable, cfg.usage_thresholds, pod_est
    )
    agg = filtering.usage_threshold_mask(
        state.node_agg_usage, state.node_allocatable,
        cfg.agg_usage_thresholds, pod_est,
    )
    agg_enabled = bool(jnp.any(cfg.agg_usage_thresholds > 0))
    thr = np.asarray((agg if agg_enabled else inst)[0]) & valid
    aff = np.asarray(pods.feasible_row(state, pod_idx)) & valid
    dev = np.ones_like(valid)
    if state.devices is not None:
        dev = np.asarray(deviceshare.device_fit_pods(state.devices, req)[0])

    feasible = fit & thr & aff & dev
    # first-fail attribution, in filter order: fit -> thresholds -> affinity
    fail_fit = valid & ~fit
    fail_thr = valid & fit & ~thr
    fail_aff = valid & fit & thr & ~aff
    fail_dev = valid & fit & thr & aff & ~dev

    # per-dim first-fail fit counts: the NumPy oracle the device kernel
    # (ops/explain.explain_counts) is tested against
    from koordinator_tpu.ops import explain as ex

    free = np.asarray(state.free)
    r = np.asarray(req)[0]
    dim_ok = (r[None, :] <= free) | (r[None, :] == 0)        # (N, R)
    fails = ~dim_ok
    prior = np.cumsum(fails, axis=-1) - fails
    ff = fails & (prior == 0)                                # (N, R)
    counts = {name: 0 for name in ex.REASON_NAMES}
    counts["node_invalid"] = int((~valid).sum())
    for d in range(ff.shape[1]):
        counts[ex.REASON_NAMES[ex.REASON_FIT_FIRST + d]] = int(
            (fail_fit & ff[:, d]).sum())
    counts["usage_threshold"] = int(fail_thr.sum())
    counts["affinity"] = int(fail_aff.sum())
    counts["device_fit"] = int(fail_dev.sum())

    return PodDiagnosis(
        total_nodes=total,
        feasible_nodes=int(feasible.sum()) if quota_admitted else 0,
        insufficient_resources=int(fail_fit.sum()),
        usage_over_threshold=int(fail_thr.sum()),
        affinity_mismatch=int(fail_aff.sum()),
        quota_rejected=not quota_admitted,
        invalid=int((~valid).sum()),
        reason_counts=counts,
        device_unfit=int(fail_dev.sum()),
    )


def diagnosis_from_counts(
    counts: np.ndarray,      # (NUM_REASONS,) int — one pod's kernel row
    feasible: int,
    total_nodes: int,
    quota_admitted: bool = True,
) -> PodDiagnosis:
    """Build a :class:`PodDiagnosis` from one row of the device kernel's
    reduction (``ops/explain.explain_counts``) — the batched replacement
    for recomputing :func:`explain_pod` per failed pod on host."""
    from koordinator_tpu.ops import explain as ex

    counts = np.asarray(counts)
    reason_counts = {
        name: int(counts[i]) for i, name in enumerate(ex.REASON_NAMES)
    }
    fit_total = int(
        counts[ex.REASON_FIT_FIRST:ex.REASON_USAGE_THRESHOLD].sum())
    return PodDiagnosis(
        total_nodes=total_nodes,
        feasible_nodes=int(feasible) if quota_admitted else 0,
        insufficient_resources=fit_total,
        usage_over_threshold=int(counts[ex.REASON_USAGE_THRESHOLD]),
        affinity_mismatch=int(counts[ex.REASON_AFFINITY]),
        quota_rejected=not quota_admitted,
        invalid=int(counts[ex.REASON_NODE_INVALID]),
        reason_counts=reason_counts,
        device_unfit=int(counts[ex.REASON_DEVICE]),
    )


def device_refusal(total_nodes: int, node: str) -> PodDiagnosis:
    """The diagnosis of a bind undone at the commit because no device
    grant could be made on its node (DeviceShare Reserve failed): the
    solve that chose the node carried no device stage."""
    from koordinator_tpu.ops import explain as ex

    counts = {name: 0 for name in ex.REASON_NAMES}
    counts["device_fit"] = 1
    return PodDiagnosis(
        total_nodes=total_nodes, feasible_nodes=0,
        insufficient_resources=0, usage_over_threshold=0,
        affinity_mismatch=0, quota_rejected=False, invalid=0,
        reason_counts=counts, device_unfit=1)
