"""Batched scheduling: score the whole (pods x nodes) problem, then assign.

Two entry points:

- :func:`score_pods` — the fully-parallel Score()/Filter() replacement: one
  shot over the (P, N) matrix, no capacity feedback between pods. This is the
  kernel the Go/py scheduler shell calls for single-pod cycles (P=1..k) and the
  benchmark target (BASELINE.md: batched Score at 1k-10k nodes).

- :func:`greedy_assign` — sequential greedy assignment with capacity feedback,
  one loop step per pod in priority order: the tensor equivalent of running the
  reference's scheduleOne loop over a whole pending queue. Each step re-filters
  and re-scores against the updated free capacity, exactly as the reference's
  snapshot would after each binding. The loop steps only over the pods that
  have a feasible node when it starts: the others could never be placed.

The scoring pipeline composes the koordinator scheduler profile's score
plugins with their weights (cmd/koord-scheduler/main.go:47-58 registry;
weights from the scheduler profile):
  final = la_w * LoadAware + fp_w * NodeResourcesFitPlus + sc_w * ScarceResourceAvoidance
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from flax import struct

from koordinator_tpu.api.resources import NUM_RESOURCE_DIMS, ResourceDim
from koordinator_tpu.ops import deviceshare, filtering, numa, scoring
from koordinator_tpu.quota.admission import charge_quota, quota_admission_mask
from koordinator_tpu.state.cluster_state import ClusterState, PodBatch


@struct.dataclass
class ScoringConfig:
    """Traced pytree of plugin weights/args (scheduler-profile equivalent)."""

    # LoadAwareScheduling args (apis/config/types.go LoadAwareSchedulingArgs)
    loadaware_resource_weights: jax.Array  # (R,) int32
    loadaware_dominant_weight: jax.Array   # () int32
    loadaware_plugin_weight: jax.Array     # () int32
    usage_thresholds: jax.Array            # (R,) int32 pct, 0 = unchecked
    agg_usage_thresholds: jax.Array        # (R,) int32 pct, 0 = unchecked
    estimator_factors: jax.Array           # (R,) int32 pct
    estimator_defaults: jax.Array          # (R,) int32

    # NodeResourcesFitPlus args
    fitplus_resource_weights: jax.Array    # (R,) int32
    fitplus_most_allocated: jax.Array      # (R,) bool
    fitplus_plugin_weight: jax.Array       # () int32

    # ScarceResourceAvoidance args
    scarce_dims: jax.Array                 # (R,) bool
    scarce_plugin_weight: jax.Array        # () int32

    @classmethod
    def default(cls) -> "ScoringConfig":
        r = NUM_RESOURCE_DIMS
        la_w = jnp.zeros(r, jnp.int32).at[ResourceDim.CPU].set(1).at[ResourceDim.MEMORY].set(1)
        factors = (
            jnp.full(r, 100, jnp.int32)
            .at[ResourceDim.CPU].set(85)      # DefaultEstimatedScalingFactors
            .at[ResourceDim.MEMORY].set(70)
        )
        defaults = (
            jnp.zeros(r, jnp.int32)
            .at[ResourceDim.CPU].set(250)     # DefaultMilliCPURequest
            .at[ResourceDim.MEMORY].set(200)  # DefaultMemoryRequest (MiB units)
        )
        fp_w = jnp.zeros(r, jnp.int32).at[ResourceDim.CPU].set(1).at[ResourceDim.MEMORY].set(1)
        return cls(
            loadaware_resource_weights=la_w,
            loadaware_dominant_weight=jnp.int32(0),
            loadaware_plugin_weight=jnp.int32(1),
            usage_thresholds=jnp.zeros(r, jnp.int32)
            .at[ResourceDim.CPU].set(65)      # defaultNodeCPUUsageThreshold
            .at[ResourceDim.MEMORY].set(95),
            agg_usage_thresholds=jnp.zeros(r, jnp.int32),
            estimator_factors=factors,
            estimator_defaults=defaults,
            fitplus_resource_weights=fp_w,
            fitplus_most_allocated=jnp.zeros(r, bool),
            fitplus_plugin_weight=jnp.int32(1),
            scarce_dims=jnp.zeros(r, bool).at[ResourceDim.GPU].set(True),
            scarce_plugin_weight=jnp.int32(0),
        )


def _composite_score(
    cfg: ScoringConfig,
    allocatable: jnp.ndarray,   # (N, R)
    requested: jnp.ndarray,     # (N, R)
    est_usage: jnp.ndarray,     # (N, R) node usage + in-flight estimates
    pod_requests: jnp.ndarray,  # (P, R)
    pod_estimated: jnp.ndarray, # (P, R)
) -> jnp.ndarray:
    """(P, N) weighted sum of score plugins."""
    la = scoring.loadaware_score(
        est_usage[None, :, :] + pod_estimated[:, None, :],
        allocatable[None, :, :],
        cfg.loadaware_resource_weights,
        cfg.loadaware_dominant_weight,
    )
    fp = scoring.fitplus_score(
        requested, allocatable, pod_requests,
        cfg.fitplus_resource_weights, cfg.fitplus_most_allocated,
    )
    sc = scoring.scarce_resource_score(pod_requests, allocatable, cfg.scarce_dims)
    return (
        la * cfg.loadaware_plugin_weight
        + fp * cfg.fitplus_plugin_weight
        + sc * cfg.scarce_plugin_weight
    )


def _threshold_mask(cfg, usage, agg_usage, allocatable, pod_est):
    """LoadAware Filter threshold selection: the aggregated-percentile policy,
    when configured, REPLACES the instantaneous thresholds (load_aware.go:150
    checks one or the other, never both)."""
    inst = filtering.usage_threshold_mask(
        usage, allocatable, cfg.usage_thresholds, pod_est
    )
    agg = filtering.usage_threshold_mask(
        agg_usage, allocatable, cfg.agg_usage_thresholds, pod_est
    )
    agg_enabled = jnp.any(cfg.agg_usage_thresholds > 0)
    return jnp.where(agg_enabled, agg, inst)


def pod_estimates(pods: PodBatch, cfg: ScoringConfig) -> jnp.ndarray:
    """(P, R) estimated usage per pod (the LoadAware estimator) — shared
    by gang_assign's inter-pass est accumulation and the incremental
    solve's pass functions, so the two pass loops cannot drift."""
    return scoring.estimate_pod_usage_by_band(
        pods.requests, cfg.estimator_factors, cfg.estimator_defaults
    )


def score_pods(
    state: ClusterState, pods: PodBatch, cfg: ScoringConfig
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One-shot batched Filter+Score (no capacity feedback).

    Returns (scores, feasible): (P, N) int32 and (P, N) bool.
    """
    pod_est = scoring.estimate_pod_usage_by_band(
        pods.requests, cfg.estimator_factors, cfg.estimator_defaults
    )
    free = state.free
    feasible = filtering.combine_masks(
        filtering.fit_mask(free, pods.requests),
        _threshold_mask(cfg, state.node_usage, state.node_agg_usage,
                        state.node_allocatable, pod_est),
        pods.feasible_rows(state),
        state.node_valid[None, :],
        pods.valid[:, None],
    )
    if state.devices is not None:
        # DeviceShare Filter: a node whose aggregate GPU row fits but no
        # device (or too few whole ones) does is not feasible
        feasible = feasible & deviceshare.device_fit_pods(
            state.devices, pods.requests)
    if state.cpus is not None:
        # NodeNUMAResource Filter: a pinned pod needs the cores, not the
        # aggregate CPU row alone
        feasible = feasible & numa.cpuset_fit_pods(state.cpus, pods)
    scores = _composite_score(
        cfg,
        state.node_allocatable,
        state.node_requested,
        state.node_usage,
        pods.requests,
        pod_est,
    )
    return scores, feasible


@struct.dataclass
class Grants:
    """What a solve's per-node resource stages granted, beside the
    assignments: the device stage's and the cpuset stage's, each None
    when the state carries no such plane.  A solve over a state with
    neither returns None in its place."""

    devices: deviceshare.DeviceGrants | None = None
    cpus: numa.CPUGrants | None = None
    #: () int32 the rows the batch rounds' Reserve loops granted on
    #: (trips x block, summed over the planes, rounds and gang passes);
    #: the exact scan grants one pod a step and adds none
    accept_rows: jax.Array | None = None


def empty_grants(state: ClusterState, p: int) -> Grants | None:
    """Nothing granted to any of ``p`` pods, per plane of ``state``."""
    if state.devices is None and state.cpus is None:
        return None
    return Grants(
        devices=(None if state.devices is None else deviceshare.DeviceGrants(
            selection=jnp.zeros((p, state.devices.shape[1]), bool),
            lost_races=jnp.zeros(p, jnp.int32))),
        cpus=(None if state.cpus is None else numa.CPUGrants(
            selection=jnp.zeros((p, state.cpus.shape[1]), bool),
            lost_races=jnp.zeros(p, jnp.int32),
            turn=jnp.zeros(p, jnp.int32))),
        accept_rows=jnp.int32(0))


def apply_grants(state: ClusterState, pods: PodBatch, node: jnp.ndarray,
                 keep: jnp.ndarray, grants: Grants | None) -> ClusterState:
    """``state`` with the kept pods' grants taken off its planes."""
    if grants is None:
        return state
    if grants.devices is not None:
        state = state.replace(devices=deviceshare.apply_grants(
            state.devices, node, keep, grants.devices.selection,
            deviceshare.pod_device_requests(pods.requests).ask))
    if grants.cpus is not None:
        state = state.replace(cpus=numa.apply_cpu_grants(
            state.cpus, node, keep, grants.cpus.selection,
            numa.pod_cpu_requests(pods)))
    return state


def merge_grants(kept: Grants | None, new: Grants | None,
                 keep: jnp.ndarray) -> Grants | None:
    """The kept pods' rows of ``new`` over ``kept``, lost races and
    accept rows summed; ``new`` took its cores after everything ``kept``
    holds, so its turns follow theirs."""
    if kept is None:
        return None

    def merged(old, cur):
        return old.replace(
            selection=jnp.where(keep[:, None], cur.selection, old.selection),
            lost_races=old.lost_races + cur.lost_races)

    cpus = None
    if kept.cpus is not None:
        old = kept.cpus
        after = jnp.max(jnp.where(jnp.any(old.selection, axis=1), old.turn,
                                  -1)) + 1
        cpus = merged(old, new.cpus).replace(
            turn=jnp.where(keep, new.cpus.turn + after, old.turn))
    return Grants(
        devices=(None if kept.devices is None
                 else merged(kept.devices, new.devices)),
        cpus=cpus,
        accept_rows=kept.accept_rows + new.accept_rows)


@struct.dataclass
class ScanStats:
    """What the exact scan did, as device scalars that leave the solve
    beside the assignments (summed over gang passes by ``gang_assign``)."""

    steps: jax.Array  # () int32 — loop trips: the rows live at entry


#: rows per block of the scan's entry filter: no (P, N) temporary wider
#: than this many rows, whatever the batch
_ENTRY_BLOCK = 1024


def scan_filter(state, rows, rows_est, cfg, requested, est_added, qstate,
                dev_free, via_rsv=None, cpu_pools=None):
    """(B, N) bool: the exact scan's Filter for a block of pod rows
    (``rows``: a :class:`PodBatch` of B rows, ``rows_est`` their (B, R)
    estimates) against a scan carry.  The scan's step (B = 1) and its
    entry filter (:func:`scan_alive`) both call it, on the single device
    and on a mesh shard, so the two cannot drift.  ``via_rsv``
    (broadcastable to (B, N)) ORs into the plain fit: the nodes a row
    reaches through a matched reservation.  ``cpu_pools`` are the CPU
    plane's pools (``ops/numa.node_pools``) the scan carries."""
    free = state.replace(node_requested=requested).free
    fits = filtering.fit_mask(free, rows.requests)
    if via_rsv is not None:
        fits = fits | via_rsv
    feasible = filtering.combine_masks(
        fits,
        # est_added accumulates in-flight pods' estimated usage (the
        # reference's pod-assign cache) on top of whichever usage base the
        # threshold policy selects.
        _threshold_mask(
            cfg,
            state.node_usage + est_added,
            state.node_agg_usage + est_added,
            state.node_allocatable,
            rows_est,
        ),
        rows.feasible_rows(state),
        state.node_valid[None, :],
        rows.valid[:, None],
    )
    if state.devices is not None:
        feasible = feasible & deviceshare.device_fit_pods(
            state.devices, rows.requests, free=dev_free)
    if state.cpus is not None:
        feasible = feasible & numa.cpuset_fit_pods(
            state.cpus, rows, pools=cpu_pools)
    if qstate is not None:
        feasible = feasible & quota_admission_mask(
            qstate, rows.requests, rows.quota_id, rows.non_preemptible,
        )[:, None]
    return feasible


def scan_alive(state, pods, pod_est_all, cfg, quota, rsv=None, match=None):
    """(P,) bool: the rows with at least one node that passes the scan's
    own Filter against the state the scan ENTERS with.

    Everything that Filter reads moves one way inside a scan (requested,
    est_added and quota ``used`` only grow; ``dev_free`` and a
    reservation's remainder only shrink), so a row that is dead here is
    dead at its own step, and its step would leave the carry bit for bit
    as it found it: skipping it is the same result (the CPU plane's
    ``held`` only grows too).  A superset of step
    feasibility, never an approximation of it: a row with a fitting
    matched reservation counts as reaching EVERY node through it (which
    node is not looked up: no (P, V) x (V, N) product), and every other
    term is the step's.

    Reduced in blocks of ``_ENTRY_BLOCK`` rows.  Over a node shard of a
    mesh this is "some LOCAL node passes": the caller merges the shards."""
    from koordinator_tpu.ops.reservation import reservation_fit

    p = pods.capacity
    block = min(p, _ENTRY_BLOCK)
    n_blocks = -(-p // block)
    # a ragged tail re-reads the last row; its copies are cut off below
    row_ids = jnp.minimum(
        jnp.arange(n_blocks * block).reshape(n_blocks, block), p - 1)
    est0 = jnp.zeros_like(state.node_usage)
    dev_free = None if state.devices is None else state.devices.free
    cpu_pools = (None if state.cpus is None
                 else numa.node_pools(state.cpus, state.cpus.held))

    def alive_block(ids):
        rows = jax.tree.map(lambda a: a[ids], pods)
        via_rsv = None
        if rsv is not None:
            via_rsv = jnp.any(
                reservation_fit(rsv, state.free, rows.requests, match[ids]),
                axis=-1)[:, None]
        return jnp.any(
            scan_filter(state, rows, pod_est_all[ids], cfg,
                        state.node_requested, est0, quota, dev_free,
                        via_rsv, cpu_pools),
            axis=-1)

    return jax.lax.map(alive_block, row_ids).reshape(-1)[:p]


def live_first(pods: PodBatch, alive: jnp.ndarray) -> jnp.ndarray:
    """(P,) the scan's visiting order: priority order (index order among
    equals) with the live rows first, their relative order untouched."""
    return jnp.lexsort((jnp.arange(pods.capacity), -pods.priority, ~alive))


def _greedy_scan(
    state: ClusterState,
    pods: PodBatch,
    cfg: ScoringConfig,
    quota=None,
    rsv=None,
    match=None,
    rsv_boost: int = 10_000,
):
    """Shared sequential-assignment scan (the single source of truth for both
    plain and reservation-aware greedy assignment): one batched entry filter
    (:func:`scan_alive`), then a loop over the live rows only, in priority
    order.  A row that is never visited keeps the -1 its step would write.

    Returns (assignments, rsv_choice, new_state, new_rsv, new_quota,
    grants, stats); the reservation outputs are None when ``rsv`` is None,
    and ``grants`` (:class:`Grants`) is None when the state carries no
    per-node plane.
    """
    from koordinator_tpu.ops.reservation import (
        allocate_from_reservation,
        nominate_reservation,
        reservation_fit,
        reservation_node_mask,
    )

    if match is not None:
        match = jnp.asarray(match)  # host producers hand over np.ndarray

    pod_est_all = scoring.estimate_pod_usage_by_band(
        pods.requests, cfg.estimator_factors, cfg.estimator_defaults
    )
    alive = scan_alive(state, pods, pod_est_all, cfg, quota, rsv, match)
    order = live_first(pods, alive)
    n_live = jnp.sum(alive, dtype=jnp.int32)

    dev = state.devices
    dreq = (None if dev is None
            else deviceshare.pod_device_requests(pods.requests))
    cpus = state.cpus
    creq = None if cpus is None else numa.pod_cpu_requests(pods)

    def step(i, carry):
        (requested, est_added, cur_rsv, qstate, dev_free,
         nodes, rsv_rows, sels, cpu_held, cpu_pools, cpu_sels) = carry
        idx = order[i]
        row = jax.tree.map(lambda a: a[idx][None], pods)
        req = pods.requests[idx]          # (R,)
        pod_est = pod_est_all[idx]        # (R,)

        via_rsv = None
        if cur_rsv is not None:
            fits_v = reservation_fit(
                cur_rsv, state.replace(node_requested=requested).free,
                req[None, :], match[idx][None])
            via_rsv = reservation_node_mask(fits_v, cur_rsv, state.capacity)
        feasible = scan_filter(
            state, row, pod_est[None, :], cfg, requested, est_added, qstate,
            dev_free, via_rsv, cpu_pools)[0]

        scores = _composite_score(
            cfg, state.node_allocatable, requested,
            state.node_usage + est_added,
            req[None, :], pod_est[None, :],
        )[0]
        if cur_rsv is not None:
            scores = scores + jnp.where(via_rsv[0], rsv_boost, 0)
        masked = jnp.where(feasible, scores, -1)
        best = jnp.argmax(masked)
        assigned = masked[best] >= 0
        node = jnp.where(assigned, best, -1)
        nodes = nodes.at[idx].set(node)

        if cur_rsv is not None:
            r_idx = nominate_reservation(fits_v, cur_rsv, node[None])[0]
            r_idx = jnp.where(assigned, r_idx, -1)
            cur_rsv, spill = allocate_from_reservation(cur_rsv, r_idx, req)
            add = jnp.where(assigned, spill, 0)
            rsv_rows = rsv_rows.at[idx].set(r_idx)
        else:
            add = jnp.where(assigned, req, 0)
        add_est = jnp.where(assigned, pod_est, 0)
        requested = requested.at[best].add(add)
        est_added = est_added.at[best].add(add_est)
        if qstate is not None:
            qstate = charge_quota(
                qstate, jnp.where(assigned, req, 0),
                jnp.where(assigned, pods.quota_id[idx], -1),
                non_preemptible=pods.non_preemptible[idx],
            )
        if dev is not None:
            # DeviceShare Reserve on the chosen node: feasibility above
            # was checked against this same free row, so a device pod
            # that is assigned is granted
            one = jax.tree.map(lambda a: a[idx][None], dreq)
            sel, _ = deviceshare.grant_rows(
                dev_free[best][None], dev.total[best][None],
                (dev.valid & dev.healthy)[best][None],
                dev.group[best][None], one)
            sel = sel[0] & assigned
            dev_free = dev_free.at[best].add(
                -(sel[:, None] * one.ask[0][None, :]))
            sels = sels.at[idx].set(sel)
        if cpus is not None:
            # NodeNUMAResource Reserve on the chosen node, against the
            # same ``held`` feasibility read
            one = jax.tree.map(lambda a: a[idx][None], creq)
            row = jax.tree.map(lambda a: a[best][None], cpus)
            sel, _ = numa.grant_cpu_rows(row, cpu_held[best][None], one)
            sel = sel[0] & assigned
            cpu_held = cpu_held.at[best].add(sel * one.unit[0])
            # the chosen node's pools alone moved: its column, not the
            # plane's, is recomputed
            cpu_pools = tuple(
                pool.at[:, best].set(col[:, 0]) for pool, col in zip(
                    cpu_pools, numa.node_pools(row, cpu_held[best][None])))
            cpu_sels = cpu_sels.at[idx].set(sel)
        return (requested, est_added, cur_rsv, qstate, dev_free,
                nodes, rsv_rows, sels, cpu_held, cpu_pools, cpu_sels)

    unplaced = jnp.full(pods.capacity, -1, jnp.int32)
    empty = empty_grants(state, pods.capacity)
    (requested, _, new_rsv, new_quota, dev_free,
     assignments, rsv_choice, selection, cpu_held, _,
     cpu_selection) = jax.lax.fori_loop(
        0, n_live, step,
        (state.node_requested, jnp.zeros_like(state.node_usage), rsv, quota,
         None if dev is None else dev.free,
         unplaced,
         None if rsv is None else unplaced,
         None if dev is None else empty.devices.selection,
         None if cpus is None else cpus.held,
         None if cpus is None else numa.node_pools(cpus, cpus.held),
         None if cpus is None else empty.cpus.selection),
    )
    new_state = state.replace(node_requested=requested)
    grants = empty
    if dev is not None:
        new_state = new_state.replace(devices=dev.replace(free=dev_free))
        grants = grants.replace(devices=grants.devices.replace(
            selection=selection))
    if cpus is not None:
        new_state = new_state.replace(cpus=cpus.replace(held=cpu_held))
        # the scan takes in its visiting order
        grants = grants.replace(cpus=grants.cpus.replace(
            selection=cpu_selection,
            turn=jnp.zeros(pods.capacity, jnp.int32).at[order].set(
                jnp.arange(pods.capacity, dtype=jnp.int32))))
    return (assignments, rsv_choice, new_state, new_rsv, new_quota, grants,
            ScanStats(steps=n_live))


def keep_planes(new_state: ClusterState, state: ClusterState) -> ClusterState:
    """The rule of every solve entry asked for no grants: the per-node
    planes (devices, CPUs) come back as they went in.  What they hold
    moves only together with a grant handed to the caller, who records
    it; a path that cannot carry the stages leaves Reserve to the commit
    (``Scheduler._grant_devices`` / ``_grant_cpusets``)."""
    if state.devices is None and state.cpus is None:
        return new_state
    return new_state.replace(devices=state.devices, cpus=state.cpus)


def greedy_assign(
    state: ClusterState,
    pods: PodBatch,
    cfg: ScoringConfig,
    quota=None,
    with_grants: bool = False,
):
    """Assign a whole pending batch sequentially in priority order.

    Returns (assignments, new_state, new_quota). new_quota is None unless a
    :class:`~koordinator_tpu.quota.QuotaDeviceState` is given, in which case
    each pod must also pass the elastic-quota admission check and Reserve-time
    quota accounting feeds back within the batch.

    assignments is (P,) int32 node index per pod (original batch order),
    -1 = unschedulable; new_state carries the updated node_requested
    accounting (Reserve semantics).

    Determinism: ties break toward the lowest node index (the reference's
    selectHost randomizes among maxima; we fix the choice for reproducibility).

    ``with_grants=True`` appends the :class:`Grants` (None for a state
    without planes), taken off ``new_state``'s planes, and the scan's
    :class:`ScanStats`; without it the planes are handed back untouched.
    """
    assignments, _, new_state, _, new_quota, grants, stats = _greedy_scan(
        state, pods, cfg, quota=quota
    )
    if with_grants:
        return assignments, new_state, new_quota, grants, stats
    return assignments, keep_planes(new_state, state), new_quota
