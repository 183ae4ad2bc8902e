"""Test-only reference for the exact greedy scan: the ``lax.scan`` that
``ops/assignment._greedy_scan`` was until it learned to step only over the
rows that are live at its entry — one sequential step for EVERY padded row,
each with its own fit mask, threshold mask, score and argmax.  The pruned
loop must equal it bit for bit (assignments, accounting, quota, reservation
remainders, device free and grants); ``compare_with_reference`` is that
check, shared by the suites of every caller of the scan."""

import jax
import jax.numpy as jnp
import numpy as np

from koordinator_tpu.ops import deviceshare, scoring
from koordinator_tpu.ops.assignment import (
    Grants,
    ScoringConfig,
    _composite_score,
    _greedy_scan,
    _threshold_mask,
    scan_alive,
)
from koordinator_tpu.quota.admission import charge_quota, quota_admission_mask
from koordinator_tpu.state.cluster_state import ClusterState, PodBatch


def scan_reference(
    state: ClusterState,
    pods: PodBatch,
    cfg: ScoringConfig,
    quota=None,
    rsv=None,
    match=None,
    rsv_boost: int = 10_000,
):
    """One step per padded row, dead or alive, in priority order.

    Returns (assignments, rsv_choice, new_state, new_rsv, new_quota,
    grants, step_feasible): ``_greedy_scan``'s first six, and (P,) bool
    "some node passed this row's OWN step's filter" in batch order.
    """
    from koordinator_tpu.ops.reservation import (
        allocate_from_reservation,
        nominate_reservation,
        reservation_fit,
        reservation_node_mask,
    )

    if match is not None:
        match = jnp.asarray(match)  # host producers hand over np.ndarray

    order = jnp.lexsort((jnp.arange(pods.capacity), -pods.priority))

    pod_est_all = scoring.estimate_pod_usage_by_band(
        pods.requests, cfg.estimator_factors, cfg.estimator_defaults
    )

    dev = state.devices
    dreq = (None if dev is None
            else deviceshare.pod_device_requests(pods.requests))

    def step(carry, idx):
        # est_added accumulates in-flight pods' estimated usage (the
        # reference's pod-assign cache) on top of whichever usage base the
        # threshold policy selects.
        requested, est_added, cur_rsv, qstate, dev_free = carry
        req = pods.requests[idx]          # (R,)
        pod_est = pod_est_all[idx]        # (R,)
        valid = pods.valid[idx]

        free = jnp.where(
            state.node_valid[:, None], state.node_allocatable - requested, 0
        )
        fits = jnp.all((req[None, :] <= free) | (req[None, :] == 0), axis=-1)
        if cur_rsv is not None:
            fits_v = reservation_fit(cur_rsv, free, req[None, :], match[idx][None])[0]
            via_rsv = reservation_node_mask(fits_v[None], cur_rsv, state.capacity)[0]
            fits = fits | via_rsv
        feasible = (
            fits
            & _threshold_mask(
                cfg,
                state.node_usage + est_added,
                state.node_agg_usage + est_added,
                state.node_allocatable,
                pod_est[None, :],
            )[0]
            & pods.feasible_row(state, idx)
            & state.node_valid
            & valid
        )
        if dev is not None:
            feasible = feasible & deviceshare.device_fit_pods(
                dev, req[None, :], free=dev_free)[0]
        if qstate is not None:
            admitted = quota_admission_mask(
                qstate, req[None, :], pods.quota_id[idx][None],
                pods.non_preemptible[idx][None],
            )[0]
            feasible = feasible & admitted

        scores = _composite_score(
            cfg, state.node_allocatable, requested,
            state.node_usage + est_added,
            req[None, :], pod_est[None, :],
        )[0]
        if cur_rsv is not None:
            scores = scores + jnp.where(via_rsv, rsv_boost, 0)
        masked = jnp.where(feasible, scores, -1)
        best = jnp.argmax(masked)
        assigned = masked[best] >= 0
        node = jnp.where(assigned, best, -1)

        if cur_rsv is not None:
            r_idx = nominate_reservation(fits_v[None], cur_rsv, node[None])[0]
            r_idx = jnp.where(assigned, r_idx, -1)
            cur_rsv, spill = allocate_from_reservation(cur_rsv, r_idx, req)
            add = jnp.where(assigned, spill, 0)
        else:
            r_idx = jnp.int32(-1)
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
        sel = None
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
        return ((requested, est_added, cur_rsv, qstate, dev_free),
                (node, r_idx, sel, jnp.any(feasible)))

    ((requested, _, new_rsv, new_quota, dev_free),
     (nodes_in_order, rsv_in_order, sel_in_order, any_in_order)) = jax.lax.scan(
        step,
        (state.node_requested, jnp.zeros_like(state.node_usage), rsv, quota,
         None if dev is None else dev.free),
        order,
    )
    assignments = jnp.full(pods.capacity, -1, jnp.int32).at[order].set(nodes_in_order)
    rsv_choice = (
        jnp.full(pods.capacity, -1, jnp.int32).at[order].set(rsv_in_order)
        if rsv is not None
        else None
    )
    new_state = state.replace(node_requested=requested)
    grants = None
    if dev is not None:
        new_state = new_state.replace(devices=dev.replace(free=dev_free))
        grants = Grants(devices=deviceshare.DeviceGrants(
            selection=jnp.zeros((pods.capacity, dev.shape[1]), bool)
            .at[order].set(sel_in_order),
            lost_races=jnp.zeros(pods.capacity, jnp.int32)),
            accept_rows=jnp.int32(0))
    step_feasible = jnp.zeros(pods.capacity, bool).at[order].set(any_in_order)
    return (assignments, rsv_choice, new_state, new_rsv, new_quota, grants,
            step_feasible)


def _leaves_equal(got, want, what):
    got_l, got_def = jax.tree.flatten(got)
    want_l, want_def = jax.tree.flatten(want)
    assert got_def == want_def, what
    for i, (g, w) in enumerate(zip(got_l, want_l)):
        np.testing.assert_array_equal(
            np.asarray(g), np.asarray(w), err_msg=f"{what} leaf {i}")


#: jitted once for every suite in the process: same shapes, one compile
_SCAN = jax.jit(_greedy_scan)
_REFERENCE = jax.jit(scan_reference)


def compare_with_reference(state, pods, cfg, quota=None, rsv=None,
                           match=None, scan=_SCAN):
    """Run the pruned scan and the reference on one problem and demand
    bit equality of everything the scan returns; also that the entry
    filter's ``alive`` covers every row a step of the reference found a
    node for (a superset, never less), and that the loop's trip count is
    the number of live rows.  Returns (assignments, steps, alive,
    step_feasible) as numpy for the caller's own assertions."""
    kw = dict(quota=quota, rsv=rsv, match=match)
    got = scan(state, pods, cfg, **kw)
    want = _REFERENCE(state, pods, cfg, **kw)
    for name, g, w in zip(
            ("assignments", "rsv_choice", "state", "rsv", "quota", "grants"),
            got, want):
        _leaves_equal(g, w, name)
    pod_est = scoring.estimate_pod_usage_by_band(
        pods.requests, cfg.estimator_factors, cfg.estimator_defaults)
    alive = np.asarray(scan_alive(
        state, pods, pod_est, cfg, quota, rsv,
        None if match is None else jnp.asarray(match)))
    step_feasible = np.asarray(want[6])
    assert not (step_feasible & ~alive).any(), (
        "rows feasible at their own step but pruned at entry: "
        f"{np.flatnonzero(step_feasible & ~alive)}")
    assert not (alive & ~np.asarray(pods.valid)).any(), "a padded row is live"
    steps = int(got[6].steps)
    assert steps == int(alive.sum())
    return np.asarray(got[0]), steps, alive, step_feasible
