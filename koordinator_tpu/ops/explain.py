"""Placement explainability: device-side reject-reason accounting.

``ops/filtering.py`` computes the per-(pod, node) reject masks on device
and ``combine_masks`` discards them — a pod that stays pending at
50k x 10,240 scale yields no answer to "which constraint killed it on
which nodes".  This module threads a compact reason taxonomy through the
same mask algebra and reduces it device-side into per-pod x per-reason
NODE COUNTS: an O(P·R_reasons) output folded out of the masks the solve
already computes, never materializing the (P, N) reason tensor on host.

Attribution is FIRST-FAIL in filter order (matching
``scheduler/diagnosis.explain_pod``): a node counts against exactly one
reason — resource fit (per dimension, first failing dim in global dim
order), then the usage threshold, then affinity/selector, then the
DeviceShare filter (a node whose aggregate GPU rows fit but on which no
device, or too few whole ones, does).  Invalid node rows count separately.  Pod-level gates (elastic-quota admission, the
gang barrier, degraded-mode suspension) have no per-node mask: their
columns exist in the taxonomy for the scheduler to fill host-side when
it attributes a failure to them (``scheduler/scheduler.py`` Diagnose).

The kernel is cheap relative to a solve — masks plus one segment
reduction, no scoring, no top-k — and the scheduler only runs it over
the COMPACTED failed rows of a round, so explain-enabled rounds with a
healthy queue pay nothing (bench_stages.py's ``explain_*`` stages guard
the <5% overhead claim at the north-star shape).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from koordinator_tpu.api.resources import NUM_RESOURCE_DIMS, ResourceDim
from koordinator_tpu.ops import deviceshare, scoring
from koordinator_tpu.state.cluster_state import ClusterState, PodBatch

# ---- reason taxonomy -------------------------------------------------------
#
# Stable column order of the (P, NUM_REASONS) counts tensor.  Do not
# reorder: dashboards, the metrics labels, and recorded explanations all
# key on these names.

REASON_NODE_INVALID = 0
#: per-dimension resource fit: column REASON_FIT_FIRST + ResourceDim
REASON_FIT_FIRST = 1
REASON_USAGE_THRESHOLD = 1 + NUM_RESOURCE_DIMS
REASON_AFFINITY = 2 + NUM_RESOURCE_DIMS
#: DeviceShare Filter: zero for a state without a device plane
REASON_DEVICE = 3 + NUM_RESOURCE_DIMS
#: pod-level gates (host-filled; the device kernel leaves them zero)
REASON_QUOTA = 4 + NUM_RESOURCE_DIMS
REASON_GANG = 5 + NUM_RESOURCE_DIMS
REASON_DEGRADED = 6 + NUM_RESOURCE_DIMS
NUM_REASONS = 7 + NUM_RESOURCE_DIMS

REASON_NAMES: tuple[str, ...] = (
    "node_invalid",
    *(f"fit_{dim.name.lower()}" for dim in ResourceDim),
    "usage_threshold",
    "affinity",
    "device_fit",
    "quota",
    "gang_barrier",
    "degraded_suspended",
)
assert len(REASON_NAMES) == NUM_REASONS

#: columns the device kernel fills (everything before the pod-level gates)
NODE_REASONS = REASON_NAMES[:REASON_QUOTA]


def fit_first_fail(free: jnp.ndarray, requests: jnp.ndarray) -> jnp.ndarray:
    """(P, N, R) bool: dimension d is the FIRST dim (global dim order)
    where the pod's request does not fit the node's free capacity.

    At most one True per (pod, node); all-False rows fit every dim.
    The complement of ``filtering.fit_mask`` attributed per-dim.
    """
    dim_ok = (requests[:, None, :] <= free[None, :, :]) | (
        requests[:, None, :] == 0)
    fails = ~dim_ok
    # fails before this dim (exclusive running count): first fail <=> no
    # earlier dim failed
    prior = jnp.cumsum(fails, axis=-1) - fails
    return fails & (prior == 0)


@jax.named_scope("explain_reduce")
def explain_counts(
    state: ClusterState, pods: PodBatch, cfg,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Device-side reject-reason accounting for a pod batch.

    Returns ``(counts, feasible)``: counts is (P, NUM_REASONS) int32 —
    per pod, how many nodes each reason eliminated (first-fail
    attribution; pod-level gate columns stay zero) — and feasible is
    (P,) int32, the nodes that survived every filter.  Row sums satisfy
    ``feasible + sum(node-reason counts) == node capacity`` for valid
    pods; invalid pod rows are all zero.

    ``cfg`` is a :class:`~koordinator_tpu.ops.assignment.ScoringConfig`
    (typed loosely to avoid the circular import).  The (P, N, R) mask
    intermediates live only inside the jit — the host only ever sees the
    O(P·NUM_REASONS) reduction.
    """
    from koordinator_tpu.ops.assignment import _threshold_mask

    pod_est = scoring.estimate_pod_usage_by_band(
        pods.requests, cfg.estimator_factors, cfg.estimator_defaults)
    valid_n = state.node_valid                          # (N,)
    pod_valid = pods.valid                              # (P,)
    base = valid_n[None, :] & pod_valid[:, None]        # (P, N)

    ff = fit_first_fail(state.free, pods.requests)      # (P, N, R)
    fit = ~jnp.any(ff, axis=-1)                         # (P, N)
    thr = _threshold_mask(cfg, state.node_usage, state.node_agg_usage,
                          state.node_allocatable, pod_est)
    aff = pods.feasible_rows(state)
    ok = base & fit & thr & aff

    fit_counts = jnp.sum((base & ~fit)[:, :, None] & ff, axis=1)  # (P, R)
    thr_fail = jnp.sum(base & fit & ~thr, axis=1)                 # (P,)
    aff_fail = jnp.sum(base & fit & thr & ~aff, axis=1)
    dev_fail = jnp.zeros(pods.capacity, jnp.int32)
    if state.devices is not None:
        dev_fit = deviceshare.device_fit_pods(state.devices, pods.requests)
        dev_fail = jnp.sum(ok & ~dev_fit, axis=1)
        ok = ok & dev_fit
    feasible = jnp.sum(ok, axis=1)
    invalid = jnp.where(pod_valid, jnp.sum(~valid_n), 0)

    counts = jnp.concatenate(
        [
            invalid[:, None],
            fit_counts,
            thr_fail[:, None],
            aff_fail[:, None],
            dev_fail[:, None],
            jnp.zeros((pods.capacity, 3), jnp.int32),   # quota/gang/degraded
        ],
        axis=1,
    ).astype(jnp.int32)
    return counts, feasible.astype(jnp.int32)


def decompose_scores(
    state: ClusterState, pods: PodBatch, cfg, cand_node: jnp.ndarray,
) -> dict[str, jnp.ndarray]:
    """Per-term score decomposition at the given candidate nodes.

    ``cand_node`` is (P, K) int32 node rows (a pod's winning node and/or
    its top-k candidates).  Returns a dict of (P, K) int32 arrays — the
    raw per-plugin scores (``loadaware``, ``fitplus``, ``scarce``) out of
    :mod:`ops/scoring` plus their weighted ``total`` — bit-identical to
    the composite ``score_pods`` computes at the same (pod, node) pairs,
    so an explanation's decomposition provably sums to the score the
    solve ranked on.
    """
    req = pods.requests                                  # (P, R)
    pod_est = scoring.estimate_pod_usage_by_band(
        req, cfg.estimator_factors, cfg.estimator_defaults)
    alloc = state.node_allocatable[cand_node]            # (P, K, R)
    requested = state.node_requested[cand_node]
    usage = state.node_usage[cand_node]

    la = scoring.loadaware_score(
        usage + pod_est[:, None, :], alloc,
        cfg.loadaware_resource_weights, cfg.loadaware_dominant_weight)

    # NodeResourcesFitPlus at gathered (P, K, R) node rows — the same
    # math as scoring.fitplus_score, whose signature is (N, R)-shaped
    combined = requested + req[:, None, :]
    least = scoring.least_requested_score(combined, alloc)
    most = scoring.most_requested_score(combined, alloc)
    per_res = jnp.where(cfg.fitplus_most_allocated, most, least)
    req_mask = (req > 0)[:, None, :]
    w = jnp.where(req_mask, cfg.fitplus_resource_weights.astype(jnp.int32), 0)
    num = jnp.sum(per_res * w, axis=-1)
    den = jnp.sum(w, axis=-1)
    fp = jnp.where(den > 0,
                   scoring.exact_floordiv(num, jnp.maximum(den, 1)),
                   scoring.MAX_NODE_SCORE)

    # ScarceResourceAvoidance at gathered rows
    node_has = alloc > 0
    pod_wants = (req > 0)[:, None, :]
    diff = node_has & ~pod_wants
    inter = diff & cfg.scarce_dims
    n_diff = jnp.sum(diff, axis=-1).astype(jnp.int32)
    n_inter = jnp.sum(inter, axis=-1).astype(jnp.int32)
    sc = scoring.exact_floordiv(
        (n_diff - n_inter) * scoring.MAX_NODE_SCORE, jnp.maximum(n_diff, 1))
    sc = jnp.where((n_diff == 0) | (n_inter == 0), scoring.MAX_NODE_SCORE, sc)

    total = (la * cfg.loadaware_plugin_weight
             + fp * cfg.fitplus_plugin_weight
             + sc * cfg.scarce_plugin_weight)
    return {"loadaware": la, "fitplus": fp, "scarce": sc, "total": total}
