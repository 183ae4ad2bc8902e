"""Sharded solve == unsharded solve on the virtual 8-device mesh."""

import jax
import numpy as np
import pytest

from koordinator_tpu.api.resources import NUM_RESOURCE_DIMS, ResourceDim
from koordinator_tpu.ops.assignment import ScoringConfig, greedy_assign, score_pods
from koordinator_tpu.parallel import mesh as pmesh
from koordinator_tpu.state.cluster_state import ClusterState, PodBatch

R = NUM_RESOURCE_DIMS
CPU, MEM = ResourceDim.CPU, ResourceDim.MEMORY


def build_problem(n_nodes=64, n_pods=32, seed=3):
    rng = np.random.default_rng(seed)
    alloc = np.zeros((n_nodes, R), np.int32)
    alloc[:, CPU] = rng.integers(8_000, 64_000, n_nodes)
    alloc[:, MEM] = rng.integers(16_384, 262_144, n_nodes)
    usage = (alloc * rng.random((n_nodes, R)) * 0.5).astype(np.int32)
    state = ClusterState.from_arrays(alloc, usage=usage, capacity=n_nodes)
    req = np.zeros((n_pods, R), np.int32)
    req[:, CPU] = rng.integers(100, 4_000, n_pods)
    req[:, MEM] = rng.integers(128, 8_192, n_pods)
    prio = rng.integers(3000, 9999, n_pods).astype(np.int32)
    pods = PodBatch.build(req, priority=prio, node_capacity=n_nodes, capacity=n_pods)
    return state, pods


def test_eight_virtual_devices():
    assert len(jax.devices()) == 8


def test_resolve_solver_mesh_2d_env_overrides(monkeypatch):
    """ISSUE 14: KOORD_SOLVER_MESH=PxN builds the explicit 2-D mesh;
    KOORD_SOLVER_MESH_PODS splits the pods axis off "auto"; the default
    (pods_axis=1) reproduces today's all-nodes layout exactly."""
    monkeypatch.delenv("KOORD_SOLVER_MESH", raising=False)
    monkeypatch.delenv("KOORD_SOLVER_MESH_PODS", raising=False)
    default = pmesh.resolve_solver_mesh("auto")
    assert default == pmesh.solver_mesh(pods_axis=1)
    assert pmesh.mesh_axes(default) == {"pods": 1, "nodes": 8}

    monkeypatch.setenv("KOORD_SOLVER_MESH", "2x4")
    m = pmesh.resolve_solver_mesh("auto")
    assert pmesh.mesh_axes(m) == {"pods": 2, "nodes": 4}
    assert pmesh.pods_shard_count(m) == 2
    assert pmesh.nodes_shard_count(m) == 4

    monkeypatch.setenv("KOORD_SOLVER_MESH", "4x4")
    import pytest

    with pytest.raises(ValueError, match="needs 16 devices"):
        pmesh.resolve_solver_mesh("auto")

    monkeypatch.delenv("KOORD_SOLVER_MESH")
    monkeypatch.setenv("KOORD_SOLVER_MESH_PODS", "4")
    m = pmesh.resolve_solver_mesh("auto")
    assert pmesh.mesh_axes(m) == {"pods": 4, "nodes": 2}

    assert pmesh.mesh_axes(None) is None
    assert pmesh.pods_shard_count(None) == 1


def test_sharded_score_matches_unsharded():
    state, pods = build_problem()
    cfg = ScoringConfig.default()
    scores_ref, feas_ref = jax.jit(score_pods)(state, pods, cfg)

    mesh = pmesh.solver_mesh(pods_axis=2)
    sstate = pmesh.shard_cluster_state(state, mesh)
    spods = pmesh.shard_pod_batch(pods, mesh)
    scores_sh, feas_sh = jax.jit(score_pods)(sstate, spods, cfg)

    assert np.array_equal(np.asarray(scores_ref), np.asarray(scores_sh))
    assert np.array_equal(np.asarray(feas_ref), np.asarray(feas_sh))


def test_sharded_greedy_assign_matches_unsharded():
    state, pods = build_problem()
    cfg = ScoringConfig.default()
    a_ref, st_ref, _ = jax.jit(greedy_assign)(state, pods, cfg)

    mesh = pmesh.solver_mesh()  # all devices on the nodes axis
    sstate = pmesh.shard_cluster_state(state, mesh)
    a_sh, st_sh, _ = jax.jit(greedy_assign)(sstate, pods, cfg)

    assert np.array_equal(np.asarray(a_ref), np.asarray(a_sh))
    assert np.array_equal(
        np.asarray(st_ref.node_requested), np.asarray(st_sh.node_requested)
    )


def test_sharded_batch_assign_matches_unsharded():
    state, pods = build_problem()
    cfg = ScoringConfig.default()
    from koordinator_tpu.ops.batch_assign import batch_assign

    f = jax.jit(batch_assign, static_argnames=("k", "rounds"))
    a_ref, st_ref, _ = f(state, pods, cfg, k=8, rounds=4)

    mesh = pmesh.solver_mesh(pods_axis=2)
    sstate = pmesh.shard_cluster_state(state, mesh)
    spods = pmesh.shard_pod_batch(pods, mesh)
    a_sh, st_sh, _ = f(sstate, spods, cfg, k=8, rounds=4)

    assert np.array_equal(np.asarray(a_ref), np.asarray(a_sh))
    assert np.array_equal(
        np.asarray(st_ref.node_requested), np.asarray(st_sh.node_requested)
    )


def test_sharded_batch_assign_matches_across_shard_counts():
    """Node-axis GSPMD placement at 2/4/8-way widths: the whole batch
    solve is width-invariant, not just 8-way (the shard_map path has its
    own 1/2/4/8 sweep in tests/test_sharded_solve.py)."""
    state, pods = build_problem()
    cfg = ScoringConfig.default()
    from koordinator_tpu.ops.batch_assign import batch_assign

    f = jax.jit(batch_assign, static_argnames=("k", "rounds"))
    a_ref, st_ref, _ = f(state, pods, cfg, k=8, rounds=4)
    for d in (2, 8):
        mesh = pmesh.solver_mesh(jax.devices()[:d])
        sstate = pmesh.shard_cluster_state(state, mesh)
        a_sh, st_sh, _ = f(sstate, pods, cfg, k=8, rounds=4)
        assert np.array_equal(np.asarray(a_ref), np.asarray(a_sh)), d
        assert np.array_equal(
            np.asarray(st_ref.node_requested),
            np.asarray(st_sh.node_requested)), d


def test_sharded_reservation_assign_matches_unsharded():
    """Reservation-first exact solve on the mesh == single-device
    (ISSUE 10 satellite: reservation solves join the parity suite)."""
    from koordinator_tpu.ops.reservation import (
        ReservationSet,
        reservation_greedy_assign,
    )

    state, pods = build_problem(n_pods=24)
    cfg = ScoringConfig.default()
    n_rsv = 4
    rsv_req = np.zeros((n_rsv, R), np.int32)
    rsv_req[:, CPU] = 4_000
    rsv_req[:, MEM] = 8_192
    rsv = ReservationSet.build(rsv_req, np.arange(n_rsv, dtype=np.int32))
    match = np.zeros((pods.capacity, rsv.capacity), bool)
    match[:8, :n_rsv] = True
    f = jax.jit(reservation_greedy_assign)
    ref = f(state, pods, cfg, rsv, match)
    mesh = pmesh.solver_mesh()
    sstate = pmesh.shard_cluster_state(state, mesh)
    got = f(sstate, pods, cfg, pmesh.shard_reservation_set(rsv, mesh),
            match)
    for i, name in enumerate(("assignments", "rsv_choice")):
        assert np.array_equal(np.asarray(got[i]), np.asarray(ref[i])), name
    assert int((np.asarray(got[1]) >= 0).sum()) > 0


def test_sharded_gang_quota_assign_matches_unsharded():
    """Gang all-or-nothing + elastic-quota admission on the mesh equals the
    single-device solve (multi-device gang+quota parity)."""
    from koordinator_tpu.ops.gang import GangInfo, gang_assign
    from koordinator_tpu.quota.admission import QuotaDeviceState
    from koordinator_tpu.quota.tree import UNBOUNDED, QuotaTree

    state, pods = build_problem(n_pods=32)
    gang_id = np.full(pods.capacity, -1, np.int32)
    gang_id[:8] = 0
    gang_id[8:12] = 1
    quota_id = np.full(pods.capacity, -1, np.int32)

    total = np.zeros(R, np.int64)
    total[CPU] = 60_000
    tree = QuotaTree(total)
    mx = np.full(R, UNBOUNDED, np.int64)
    mx[CPU] = 24_000
    mn = np.zeros(R, np.int64)
    tree.add("q", min=mn, max=mx)
    tree.set_request("q", total)
    tree.refresh_runtime()
    quota, index = QuotaDeviceState.from_tree(tree)
    quota_id[12:24] = index["q"]

    pods = pods.replace(
        gang_id=np.asarray(gang_id), quota_id=np.asarray(quota_id)
    )
    gangs = GangInfo.build(np.array([6, 4], np.int32))
    cfg = ScoringConfig.default()

    f = jax.jit(gang_assign, static_argnames=("passes",))
    a_ref, st_ref, q_ref = f(state, pods, cfg, gangs, quota, passes=2)

    mesh = pmesh.solver_mesh(pods_axis=2)
    sstate = pmesh.shard_cluster_state(state, mesh)
    spods = pmesh.shard_pod_batch(pods, mesh)
    a_sh, st_sh, q_sh = f(sstate, spods, cfg, gangs, quota, passes=2)

    assert np.array_equal(np.asarray(a_ref), np.asarray(a_sh))
    assert np.array_equal(
        np.asarray(st_ref.node_requested), np.asarray(st_sh.node_requested)
    )
    assert np.array_equal(
        np.asarray(q_ref.headroom), np.asarray(q_sh.headroom)
    )


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_sharded_twin_with_dead_rows_equals_the_single_device_scan(seed):
    """The shard_map twin of the exact scan (entry filter on each node
    shard, one pmax, then the loop over the live rows) against the single-
    device scan and the one-step-per-row reference: dead rows (too wide for
    any node), rows whose only nodes sit on ONE shard, padded rows, and a
    quota that runs out mid-scan."""
    import jax.numpy as jnp

    from koordinator_tpu.ops.gang import GangInfo
    from koordinator_tpu.parallel import sharded as ps
    from tests.problem_helpers import build_problem as factored_problem
    from tests.scan_reference import compare_with_reference
    from tests.test_quota import leaves_under_a_parent

    n = 100
    state, pods = factored_problem(n_nodes=64, n_pods=n, seed=seed)
    rng = np.random.default_rng(seed)
    requests = np.asarray(pods.requests).copy()
    dead = np.zeros(pods.capacity, bool)
    dead[:n] = rng.random(n) < 0.3
    requests[dead, CPU] = 1_000_000
    # pods 0-9 fit only nodes 56-63: alive on the last shard alone
    selector = np.asarray(pods.selector_mask).copy()
    node_class = np.asarray(state.node_class).copy()
    node_class[56:] = 7
    selector[:, 7] = False
    selector[:10] = False
    selector[:10, 7] = True
    quota_id = np.full(pods.capacity, -1, np.int32)
    quota_id[:n] = rng.integers(-1, 3, n)
    pods = pods.replace(requests=jnp.asarray(requests),
                        selector_mask=jnp.asarray(selector),
                        quota_id=jnp.asarray(quota_id))
    state = state.replace(node_class=jnp.asarray(node_class))
    quota = leaves_under_a_parent([30_000, 900_000], parent_cpu=2_000_000)
    cfg = ScoringConfig.default()

    want_a, steps, alive, _ = compare_with_reference(state, pods, cfg,
                                                     quota=quota)
    assert 0 < steps < n and not alive[dead].any()
    mesh = pmesh.solver_mesh()
    a, st, q = ps.sharded_greedy_assign(mesh, state, pods, cfg, quota)
    ref_a, ref_st, ref_q = jax.jit(greedy_assign)(state, pods, cfg, quota)
    np.testing.assert_array_equal(np.asarray(a), want_a)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(ref_a))
    np.testing.assert_array_equal(np.asarray(st.node_requested),
                                  np.asarray(ref_st.node_requested))
    np.testing.assert_array_equal(np.asarray(q.headroom),
                                  np.asarray(ref_q.headroom))
    # the gang twin hands the same step count out as the single device
    gangs = GangInfo.build(np.zeros(0, np.int32))
    ga, _, _, stats = ps.sharded_gang_assign(
        mesh, state, pods, cfg, gangs, quota, passes=1, solver="greedy")
    np.testing.assert_array_equal(np.asarray(ga), want_a)
    assert int(stats.steps) == steps
