"""Reservation semantics: restore, policies, allocate-once, lifecycle.

Covers the reference behaviors in pkg/scheduler/plugins/reservation/
(transformer restore, Aligned/Restricted fit, nominator best-fit, Reserve
accounting) and the Pending->Available->Expired phase machine.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from koordinator_tpu.api.resources import NUM_RESOURCE_DIMS, ResourceDim
from koordinator_tpu.ops.assignment import ScoringConfig
from koordinator_tpu.ops.reservation import (
    ReservationSet,
    allocate_from_reservation,
    nominate_reservation,
    reservation_fit,
    reservation_greedy_assign,
    score_pods_with_reservations,
)
from koordinator_tpu.scheduler.reservations import (
    OwnerMatcher,
    ReservationCache,
    ReservationPhase,
    ReservationSpec,
)
from koordinator_tpu.scheduler.snapshot import ClusterSnapshot, NodeSpec, PodSpec
from koordinator_tpu.state.cluster_state import ClusterState, PodBatch
from tests.scan_reference import compare_with_reference

R = NUM_RESOURCE_DIMS
CPU, MEM = ResourceDim.CPU, ResourceDim.MEMORY


def vec(cpu=0, mem=0):
    v = np.zeros(R, np.int32)
    v[CPU], v[MEM] = cpu, mem
    return v


def mk_state(node_cpus, requested_cpus=None, mem=65_536):
    alloc = np.zeros((len(node_cpus), R), np.int32)
    alloc[:, CPU] = node_cpus
    alloc[:, MEM] = mem
    req = None
    if requested_cpus is not None:
        req = np.zeros_like(alloc)
        req[:, CPU] = requested_cpus
    return ClusterState.from_arrays(alloc, requested=req)


def mk_pods(cpus, state, mem=1_024):
    req = np.zeros((len(cpus), R), np.int32)
    req[:, CPU] = cpus
    req[:, MEM] = mem
    return PodBatch.build(req, node_capacity=state.capacity)


def quiet_cfg():
    return ScoringConfig.default().replace(
        usage_thresholds=jnp.zeros(R, jnp.int32),
        estimator_defaults=jnp.zeros(R, jnp.int32),
    )


def one_reservation(node=0, cpu=4_000, mem=8_192, **kw):
    return ReservationSet.build(
        np.stack([vec(cpu, mem)]), np.array([node]), **kw
    )


def test_non_owner_cannot_use_reserved_capacity():
    # Node 0: 10 cores, 8 of which are reserved (charged to node_requested).
    state = mk_state([10_000], requested_cpus=[8_000])
    pods = mk_pods([4_000], state)
    rsv = one_reservation(node=0, cpu=8_000)
    match = jnp.zeros((pods.capacity, rsv.capacity), bool)  # not an owner
    _, feasible, _ = jax.jit(score_pods_with_reservations)(
        state, pods, quiet_cfg(), rsv, match
    )
    assert not bool(feasible[0, 0])


def test_owner_fits_via_reservation_restore():
    state = mk_state([10_000], requested_cpus=[8_000])
    pods = mk_pods([4_000], state)
    rsv = one_reservation(node=0, cpu=8_000)
    match = jnp.zeros((pods.capacity, rsv.capacity), bool).at[0, 0].set(True)
    scores, feasible, fits = jax.jit(score_pods_with_reservations)(
        state, pods, quiet_cfg(), rsv, match
    )
    assert bool(feasible[0, 0]) and bool(fits[0, 0])


def test_aligned_spill_uses_node_free():
    # 2 cores free on the node + 3 reserved => a 4-core owner pod fits (Aligned).
    state = mk_state([10_000], requested_cpus=[8_000])  # free = 2000
    pods = mk_pods([4_000], state)
    rsv = one_reservation(node=0, cpu=3_000)
    match = jnp.ones((pods.capacity, rsv.capacity), bool)
    fits = reservation_fit(rsv, state.free, pods.requests, match)
    assert bool(fits[0, 0])


def test_restricted_blocks_spill_on_reserved_dims():
    state = mk_state([10_000], requested_cpus=[8_000])  # free = 2000
    pods = mk_pods([4_000], state)
    rsv = one_reservation(node=0, cpu=3_000, restricted=np.array([True]))
    match = jnp.ones((pods.capacity, rsv.capacity), bool)
    fits = reservation_fit(rsv, state.free, pods.requests, match)
    assert not bool(fits[0, 0])  # 4000 > 3000 remaining, spill not allowed
    small = mk_pods([3_000], state)
    fits2 = reservation_fit(rsv, state.free, small.requests, match)
    assert bool(fits2[0, 0])


def test_nominate_prefers_best_fit():
    # Two reservations on node 0: 8-core and 3-core. A 2-core pod should take
    # the 3-core one (smallest sufficient remainder).
    state = mk_state([20_000], requested_cpus=[11_000])
    rsv = ReservationSet.build(
        np.stack([vec(8_000, 8_192), vec(3_000, 8_192)]), np.array([0, 0])
    )
    pods = mk_pods([2_000], state)
    match = jnp.ones((pods.capacity, rsv.capacity), bool)
    fits = reservation_fit(rsv, state.free, pods.requests, match)
    choice = nominate_reservation(fits, rsv, jnp.zeros(pods.capacity, jnp.int32))
    assert int(choice[0]) == 1


def test_allocate_once_consumes_everything():
    rsv = one_reservation(node=0, cpu=8_000, allocate_once=np.array([True]))
    new_rsv, spill = allocate_from_reservation(
        rsv, jnp.int32(0), jnp.asarray(vec(2_000, 512))
    )
    np.testing.assert_array_equal(
        np.asarray(new_rsv.allocated[0]), np.asarray(rsv.reserved[0])
    )
    assert int(spill[CPU]) == 0
    assert float(jnp.sum(new_rsv.remaining)) == 0


def test_greedy_assign_charges_reservation_then_node():
    # Node: 10 cores, 6 reserved. Owner pod of 8 cores: 6 from reservation,
    # 2 spill to node_requested.
    state = mk_state([10_000], requested_cpus=[6_000])
    pods = mk_pods([8_000], state, mem=1_024)
    rsv = one_reservation(node=0, cpu=6_000, mem=2_048)
    match = jnp.ones((pods.capacity, rsv.capacity), bool)
    a, rc, new_state, new_rsv, _, _ = jax.jit(reservation_greedy_assign)(
        state, pods, quiet_cfg(), rsv, match
    )
    assert int(a[0]) == 0 and int(rc[0]) == 0
    assert int(new_state.node_requested[0, CPU]) == 6_000 + 2_000
    assert int(new_rsv.allocated[0, CPU]) == 6_000


def test_greedy_assign_prefers_reserved_node():
    # Two identical nodes; reservation on node 1 => owner pod goes to node 1
    # even though node 0 is emptier by plain scoring.
    state = mk_state([10_000, 10_000], requested_cpus=[0, 4_000])
    pods = mk_pods([2_000], state)
    rsv = one_reservation(node=1, cpu=4_000)
    match = jnp.ones((pods.capacity, rsv.capacity), bool)
    a, rc, _, _, _, _ = jax.jit(reservation_greedy_assign)(
        state, pods, quiet_cfg(), rsv, match
    )
    assert int(a[0]) == 1 and int(rc[0]) == 0


def test_overloaded_node_stays_infeasible_even_for_owners():
    # Usage threshold CPU=65%; node at 90% usage. Reservation restore must not
    # bypass the LoadAware Filter.
    state = mk_state([10_000], requested_cpus=[8_000])
    state = state.replace(
        node_usage=state.node_usage.at[0, CPU].set(9_000),
        node_agg_usage=state.node_agg_usage.at[0, CPU].set(9_000),
    )
    pods = mk_pods([1_000], state)
    rsv = one_reservation(node=0, cpu=8_000)
    match = jnp.ones((pods.capacity, rsv.capacity), bool)
    cfg = ScoringConfig.default().replace(estimator_defaults=jnp.zeros(R, jnp.int32))
    _, feasible, _ = score_pods_with_reservations(state, pods, cfg, rsv, match)
    assert not bool(feasible[0, 0])


def test_unrequested_dim_negative_free_does_not_block():
    # Node shrank: allocatable < requested in MEM, pod requests only CPU.
    state = mk_state([10_000], requested_cpus=[8_000], mem=1_024)
    state = state.replace(
        node_requested=state.node_requested.at[0, MEM].set(2_048)
    )
    req = np.zeros((1, R), np.int32)
    req[0, CPU] = 3_000
    pods = PodBatch.build(req, node_capacity=state.capacity)
    rsv = one_reservation(node=0, cpu=8_000, mem=0)
    match = jnp.ones((pods.capacity, rsv.capacity), bool)
    fits = reservation_fit(rsv, state.free, pods.requests, match)
    assert bool(fits[0, 0])


def test_expire_after_node_deleted_does_not_crash():
    snap = ClusterSnapshot()
    snap.upsert_node(NodeSpec("n0", vec(10_000, 65_536)))
    snap.flush()
    cache = ReservationCache()
    cache.upsert(ReservationSpec("rsv-x", vec(4_000, 4_096), ttl_sec=10.0))
    cache.make_available("rsv-x", "n0", snap, now=0.0)
    snap.remove_node("n0")
    snap.flush()
    assert cache.expire_tick(now=11.0, snapshot=snap) == ["rsv-x"]
    assert cache.get("rsv-x").phase is ReservationPhase.EXPIRED


def test_exhausted_reservation_gets_no_boost():
    # Node 0 empty; node 1 carries a consumed allocate-once reservation.
    # Two owner pods: the first consumes it; the second must NOT be steered
    # to node 1 by a stale boost.
    state = mk_state([10_000, 10_000], requested_cpus=[0, 4_000])
    pods = mk_pods([2_000, 2_000], state)
    rsv = one_reservation(node=1, cpu=4_000, allocate_once=np.array([True]))
    match = jnp.ones((pods.capacity, rsv.capacity), bool)
    a, rc, _, _, _, _ = jax.jit(reservation_greedy_assign)(
        state, pods, quiet_cfg(), rsv, match
    )
    a, rc = np.asarray(a), np.asarray(rc)
    assert int(a[0]) == 1 and int(rc[0]) == 0      # first pod consumes it
    assert int(a[1]) == 0 and int(rc[1]) == -1     # second goes elsewhere


def test_greedy_assign_accepts_numpy_match():
    state = mk_state([10_000])
    pods = mk_pods([2_000], state)
    rsv = one_reservation(node=0, cpu=4_000)
    match = np.ones((pods.capacity, rsv.capacity), bool)  # numpy, not jnp
    a, rc, _, _, _, _ = reservation_greedy_assign(state, pods, quiet_cfg(), rsv, match)
    assert int(a[0]) == 0


def test_cache_lifecycle_and_expiration():
    snap = ClusterSnapshot()
    snap.upsert_node(NodeSpec("n0", vec(10_000, 65_536)))
    snap.flush()
    cache = ReservationCache()
    cache.upsert(
        ReservationSpec(
            "rsv-a", vec(6_000, 8_192),
            owners=[OwnerMatcher(labels={"app": "web"})],
            ttl_sec=60.0,
        )
    )
    cache.make_available("rsv-a", "n0", snap, now=100.0)
    assert cache.get("rsv-a").phase is ReservationPhase.AVAILABLE
    assert int(snap.state.node_requested[0, CPU]) == 6_000

    # Owner allocates 2 cores; on expiry only the remainder (4) returns.
    pod = PodSpec("p0", vec(2_000, 512), labels={"app": "web"})
    dev, names = cache.build_set(snap)
    match = cache.match_matrix([pod], 1, dev.capacity)
    assert match[0, 0]
    stranger = PodSpec("p1", vec(2_000, 512), labels={"app": "db"})
    assert not cache.match_matrix([stranger], 1, dev.capacity)[0, 0]

    cache.commit_allocations(names, [pod], np.array([0]), np.array([0]))
    assert cache.get("rsv-a").allocated[CPU] == 2_000

    expired = cache.expire_tick(now=161.0, snapshot=snap)
    assert expired == ["rsv-a"]
    assert cache.get("rsv-a").phase is ReservationPhase.EXPIRED
    assert int(snap.state.node_requested[0, CPU]) == 2_000  # allocated part stays


def test_allocate_once_commit_marks_succeeded():
    snap = ClusterSnapshot()
    snap.upsert_node(NodeSpec("n0", vec(10_000, 65_536)))
    snap.flush()
    cache = ReservationCache()
    cache.upsert(
        ReservationSpec(
            "rsv-b", vec(4_000, 4_096),
            owners=[OwnerMatcher(labels={"job": "x"})],
            allocate_once=True,
        )
    )
    cache.make_available("rsv-b", "n0", snap, now=0.0)
    dev, names = cache.build_set(snap)
    pod = PodSpec("p0", vec(1_000, 256), labels={"job": "x"})
    cache.commit_allocations(names, [pod], np.array([0]), np.array([0]))
    spec = cache.get("rsv-b")
    assert spec.phase is ReservationPhase.SUCCEEDED
    np.testing.assert_array_equal(spec.allocated, spec.requests)


# -- the pruned scan with reservations (tests/scan_reference.py) -------------


def test_owner_dead_by_capacity_lives_through_its_reservation():
    """The OR-side of the entry filter: both nodes are full for the plain
    fit, so every pod is dead by capacity; the owner alone reaches node 1
    through its reservation's remainder and must be stepped and placed."""
    state = mk_state([10_000, 10_000], requested_cpus=[10_000, 10_000])
    pods = mk_pods([3_000, 3_000, 3_000], state)
    rsv = one_reservation(node=1, cpu=4_000)
    match = np.zeros((pods.capacity, rsv.capacity), bool)
    match[1, 0] = True
    a, steps, alive, _ = compare_with_reference(
        state, pods, quiet_cfg(), rsv=rsv, match=match)
    assert alive[:3].tolist() == [False, True, False] and steps == 1
    assert a[:3].tolist() == [-1, 1, -1]


def test_allocate_once_reservation_taken_mid_scan():
    """Two owners of one allocate-once reservation on a full node: both
    are live at entry, the first consumes it whole, the second is turned
    away at its own step — stepped, not pruned."""
    state = mk_state([10_000], requested_cpus=[10_000])
    pods = mk_pods([1_000, 1_000, 1_000], state)
    rsv = one_reservation(node=0, cpu=4_000,
                          allocate_once=np.array([True]))
    match = np.zeros((pods.capacity, rsv.capacity), bool)
    match[:2, 0] = True
    a, steps, alive, step_feasible = compare_with_reference(
        state, pods, quiet_cfg(), rsv=rsv, match=match)
    assert alive[:3].tolist() == [True, True, False] and steps == 2
    assert step_feasible[:3].tolist() == [True, False, False]
    assert a[:3].tolist() == [0, -1, -1]


@pytest.mark.parametrize("policy", ["aligned", "restricted"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_pruned_scan_with_reservations_equals_the_stepwise_scan(seed, policy):
    """Seeded: 10 nearly full nodes, 12 reservations (some allocate-once),
    48 pods of which a third own some reservations and a quarter fit
    nothing; remainders run out mid-scan."""
    rng = np.random.default_rng(seed)
    n_nodes, n_rsv, n = 10, 12, 48
    node_cpu = np.full(n_nodes, 32_000)
    rsv_node = rng.integers(0, n_nodes, n_rsv)
    rsv_cpu = rng.integers(2_000, 6_000, n_rsv)
    reserved = np.zeros(n_nodes, np.int64)
    np.add.at(reserved, rsv_node, rsv_cpu)
    # the reserve-pod trick: reserved capacity is already requested; little
    # is free beside it
    state = mk_state(node_cpu, requested_cpus=np.minimum(
        node_cpu, reserved + rng.integers(18_000, 30_000, n_nodes)))
    cpus = rng.integers(500, 5_000, n)
    cpus[rng.random(n) < 0.25] = 40_000
    req = np.zeros((n, R), np.int32)
    req[:, CPU], req[:, MEM] = cpus, 256
    pods = PodBatch.build(
        req, priority=rng.integers(5_000, 5_003, n).astype(np.int32),
        node_capacity=state.capacity)
    rsv = ReservationSet.build(
        np.stack([vec(c, 4_096) for c in rsv_cpu]), rsv_node,
        allocate_once=rng.random(n_rsv) < 0.3,
        restricted=np.full(n_rsv, policy == "restricted"))
    match = np.zeros((pods.capacity, rsv.capacity), bool)
    owners = rng.random(n) < 0.35
    match[:n, :n_rsv] = owners[:, None] & (rng.random((n, n_rsv)) < 0.4)
    a, steps, alive, step_feasible = compare_with_reference(
        state, pods, quiet_cfg(), rsv=rsv, match=match)
    assert not alive[:n][cpus == 40_000].any()
    assert 0 < int((a >= 0).sum()) <= steps < n
