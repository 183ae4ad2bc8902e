"""Gang (coscheduling) all-or-nothing assignment.

The reference implements gangs with a Permit-phase wait: each gang pod parks
until minMember of its gang have Reserved, then the whole gang group is
allowed to bind (``coscheduling/core/core.go:544 Permit``, ``:640
AllowGangGroup``); a timeout unreserves everything. Gang *groups* tie several
gangs together — all gangs in a group must reach minMember or none binds.

The tensor equivalent replaces park-and-wait with solve-and-rollback:

1. run the greedy batch solve (tentative Reserve for everyone),
2. count per-gang placements with a segment-sum, test ``count >= minMember``,
3. propagate failure through gang groups (a group fails if any member fails),
4. roll back every pod of a failed group — assignments, node accounting and
   quota charges — in one scatter, and
5. optionally re-solve with the freed capacity (failed gangs retry next cycle
   in the reference; extra passes here let non-gang pods reclaim capacity a
   failed gang transiently held).

PreEnqueue parity: a gang whose *pending* pod count is below minMember never
enters the solve (``core.go:212 PreEnqueue``) — its pods are masked invalid up
front.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

from koordinator_tpu.ops import deviceshare
from koordinator_tpu.ops.assignment import ScoringConfig, greedy_assign
from koordinator_tpu.quota.admission import charge_quota_batch
from koordinator_tpu.state.cluster_state import ClusterState, PodBatch


@struct.dataclass
class GangInfo:
    """Gang definitions, shape (G,). Mirrors PodGroup spec (minMember,
    gang-group annotation)."""

    min_member: jax.Array  # (G,) int32
    group_id: jax.Array    # (G,) int32 — gangs sharing a group live or die together
    valid: jax.Array       # (G,) bool

    @property
    def capacity(self) -> int:
        return self.min_member.shape[0]

    @classmethod
    def build(
        cls,
        min_member: np.ndarray,
        group_id: np.ndarray | None = None,
        capacity: int | None = None,
    ) -> "GangInfo":
        g = len(min_member)
        cap = capacity if capacity is not None else max(8, g)
        mm = np.zeros(cap, np.int32)
        mm[:g] = min_member
        gid = np.arange(cap, dtype=np.int32)
        if group_id is not None:
            gid[:g] = group_id
        valid = np.zeros(cap, bool)
        valid[:g] = True
        return cls(
            min_member=jnp.asarray(mm),
            group_id=jnp.asarray(gid),
            valid=jnp.asarray(valid),
        )


def _per_gang_counts(flags: jnp.ndarray, gang_id: jnp.ndarray, g: int) -> jnp.ndarray:
    """Sum boolean flags per gang; gang_id -1 lands in an overflow bucket."""
    gid = jnp.where(gang_id >= 0, gang_id, g)
    return jax.ops.segment_sum(flags.astype(jnp.int32), gid, num_segments=g + 1)[:g]


def _group_ok(gang_ok: jnp.ndarray, gangs: GangInfo) -> jnp.ndarray:
    """(G,) bool: True when every valid gang in the same group satisfied min."""
    g = gangs.capacity
    fails = jax.ops.segment_sum(
        (~gang_ok & gangs.valid).astype(jnp.int32), gangs.group_id, num_segments=g
    )
    return fails[gangs.group_id] == 0


def pre_enqueue_mask(pods: PodBatch, gangs: GangInfo) -> jnp.ndarray:
    """(P,) bool: gang pods are schedulable only when their gang has at least
    minMember pending pods (PreEnqueue parity)."""
    g = gangs.capacity
    pending = _per_gang_counts(pods.valid, pods.gang_id, g)
    gang_ready = pending >= gangs.min_member
    pod_gang = jnp.maximum(pods.gang_id, 0)
    return (pods.gang_id < 0) | gang_ready[pod_gang]


def rollback_failed_gangs(
    assignments: jnp.ndarray,
    state_before: ClusterState,
    pods: PodBatch,
    gangs: GangInfo,
    prior_kept: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, ClusterState, jnp.ndarray, jnp.ndarray]:
    """Undo every assignment belonging to a gang group that missed minMember.

    ``prior_kept`` (P,) marks pods already bound in earlier passes: their gang
    membership counts toward minMember (an already-permitted gang's surplus
    pods bind freely, as with the reference's Permit), but they are not
    re-assigned here.

    Returns (final_assignments, state, keep_mask, failed_mask). node_requested
    is rebuilt from state_before plus only this pass's kept pods, so rollback
    is exact; failed_mask marks pods of rolled-back gangs (they back off for
    the rest of the batch, as a failed gang waits for the next cycle upstream).
    """
    g = gangs.capacity
    assigned = (assignments >= 0) & pods.valid
    counted = assigned if prior_kept is None else (assigned | prior_kept)
    counts = _per_gang_counts(counted, pods.gang_id, g)
    gang_ok = (counts >= gangs.min_member) & gangs.valid
    ok = _group_ok(gang_ok, gangs)
    pod_gang = jnp.maximum(pods.gang_id, 0)
    keep = assigned & ((pods.gang_id < 0) | ok[pod_gang])

    final = jnp.where(keep, assignments, -1)
    node = jnp.where(keep, assignments, 0)
    add = jnp.where(keep[:, None], pods.requests, 0)
    node_requested = state_before.node_requested.at[node].add(add)
    failed = (pods.gang_id >= 0) & ~ok[pod_gang] & pods.valid
    return final, state_before.replace(node_requested=node_requested), keep, failed


def gang_assign(
    state: ClusterState,
    pods: PodBatch,
    cfg: ScoringConfig,
    gangs: GangInfo,
    quota=None,
    passes: int = 2,
    solver: str = "greedy",
    method: str = "auto",
    with_grants: bool = False,
):
    """Batch assignment with gang all-or-nothing semantics.

    Returns (assignments, state, quota) as :func:`greedy_assign` does (quota
    is None when not given), and after them with ``with_grants`` the device
    grants and the exact scans' :class:`~koordinator_tpu.ops.assignment.
    ScanStats` summed over the passes (None from the batch engine, which
    runs no scan). ``passes`` > 1 re-solves leftover pods after
    failed-gang rollback so freed capacity is reclaimed within the batch.

    ``solver`` picks the per-pass assignment engine: ``"greedy"`` is the
    exact sequential scan (per-pod capacity feedback, strict priority
    order); ``"batch"`` is the data-parallel propose/accept solve
    (ops/batch_assign.py) — the throughput path for large queues, with
    round-granular feedback and top-k candidate restriction. Gang
    rollback/all-or-nothing semantics are identical either way (they act
    on the assignment vector).  ``method`` passes through to the batch
    solver's candidate selection (batch_assign.CANDIDATE_METHODS), so
    gang solves can force the chunked/approx paths too.
    """
    from koordinator_tpu.ops.assignment import (
        ScanStats,
        keep_devices,
        pod_estimates,
    )
    from koordinator_tpu.ops.batch_assign import batch_assign

    if solver not in ("greedy", "batch"):
        raise ValueError(f"unknown solver {solver!r}")
    from koordinator_tpu.ops.batch_assign import CANDIDATE_METHODS

    if method not in CANDIDATE_METHODS:
        raise ValueError(f"unknown candidate method {method!r}; "
                         f"one of {CANDIDATE_METHODS}")
    if solver == "greedy" and method != "auto":
        # the sequential scan has no candidate stage: a forced method
        # that silently did nothing would fake a measurement
        raise ValueError('method applies only to solver="batch"')

    pre_ok = pre_enqueue_mask(pods, gangs)
    active_pods = pods.replace(valid=pods.valid & pre_ok)

    total = jnp.full(pods.capacity, -1, jnp.int32)
    kept_so_far = jnp.zeros(pods.capacity, bool)
    cur_state = state
    cur_quota = quota
    # Estimated usage of pods kept in earlier passes (the reference's
    # pod-assign cache): later passes must filter/score against it, else they
    # overcommit past the load thresholds a single-pass solve would enforce.
    pod_est_all = pod_estimates(pods, cfg)
    est_accum = jnp.zeros_like(state.node_usage)
    # the device stage's outputs, kept like the assignments: a rolled-
    # back gang's grants go back with its node accounting
    grants = None
    if state.devices is not None:
        dev_ask = deviceshare.pod_device_requests(pods.requests).ask
        grants = deviceshare.DeviceGrants(
            selection=jnp.zeros((pods.capacity, state.devices.shape[1]),
                                bool),
            lost_races=jnp.zeros(pods.capacity, jnp.int32))
    stats = ScanStats(steps=jnp.int32(0)) if solver == "greedy" else None

    for _ in range(passes):
        with jax.named_scope("gang_pass"):
            solve_state = cur_state.replace(
                node_usage=cur_state.node_usage + est_accum,
                node_agg_usage=cur_state.node_agg_usage + est_accum,
            )
            if solver == "batch":
                a, _, _, g = batch_assign(
                    solve_state, active_pods, cfg, cur_quota,
                    method=method, with_grants=True)
            else:
                a, _, _, g, scanned = greedy_assign(
                    solve_state, active_pods, cfg, cur_quota,
                    with_grants=True)
                stats = jax.tree.map(jnp.add, stats, scanned)

            final, cur_state, keep, failed = rollback_failed_gangs(
                a, cur_state, active_pods, gangs, prior_kept=kept_so_far
            )
            node = jnp.where(keep, final, 0)
            if grants is not None:
                # rebuilt like node_requested: the state before the pass
                # plus the kept pods' grants alone
                cur_state = cur_state.replace(
                    devices=deviceshare.apply_grants(
                        cur_state.devices, node, keep, g.selection,
                        dev_ask))
                grants = deviceshare.DeviceGrants(
                    selection=jnp.where(keep[:, None], g.selection,
                                        grants.selection),
                    lost_races=grants.lost_races + g.lost_races)
            est_accum = est_accum.at[node].add(
                jnp.where(keep[:, None], pod_est_all, 0)
            )
            if cur_quota is not None:
                cur_quota = charge_quota_batch(
                    cur_quota, active_pods.requests, active_pods.quota_id,
                    keep, active_pods.non_preemptible,
                )
            total = jnp.where(keep, final, total)
            kept_so_far = kept_so_far | keep
            # next pass: still-unassigned pods stay in play, but rolled-back gangs
            # back off for the rest of the batch (retry next cycle upstream)
            active_pods = active_pods.replace(
                valid=active_pods.valid & ~keep & ~failed
            )

    if with_grants:
        return total, cur_state, cur_quota, grants, stats
    return total, keep_devices(cur_state, state), cur_quota
