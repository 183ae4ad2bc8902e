import jax
import jax.numpy as jnp
import numpy as np
import pytest

from koordinator_tpu.api.resources import NUM_RESOURCE_DIMS, ResourceDim
from koordinator_tpu.ops.assignment import ScoringConfig
from koordinator_tpu.ops.gang import GangInfo, gang_assign, pre_enqueue_mask
from koordinator_tpu.state.cluster_state import ClusterState, PodBatch

R = NUM_RESOURCE_DIMS
CPU, MEM = ResourceDim.CPU, ResourceDim.MEMORY


def mk_state(node_cpus, mem=65_536):
    alloc = np.zeros((len(node_cpus), R), np.int32)
    alloc[:, CPU] = node_cpus
    alloc[:, MEM] = mem
    return ClusterState.from_arrays(alloc)


def mk_pods(cpus, gang_id, state, mem=1_024, priority=None):
    req = np.zeros((len(cpus), R), np.int32)
    req[:, CPU] = cpus
    req[:, MEM] = mem
    return PodBatch.build(
        req,
        gang_id=np.asarray(gang_id, np.int32),
        priority=None if priority is None else np.asarray(priority, np.int32),
        node_capacity=state.capacity,
    )


def cfg():
    return ScoringConfig.default().replace(
        usage_thresholds=jnp.zeros(R, jnp.int32),
        estimator_defaults=jnp.zeros(R, jnp.int32),
    )


def test_gang_satisfied_schedules_all():
    state = mk_state([10_000, 10_000])
    pods = mk_pods([4_000] * 4, [0, 0, 0, 0], state)
    gangs = GangInfo.build(np.array([4]))
    a, _, _ = jax.jit(gang_assign, static_argnames="passes")(
        state, pods, cfg(), gangs
    )
    assert (np.asarray(a)[:4] >= 0).all()


def test_gang_unsatisfiable_rolls_back_all():
    # only 3 of the 4 gang pods can fit -> whole gang rolls back
    state = mk_state([10_000])
    pods = mk_pods([3_000] * 4, [0, 0, 0, 0], state)
    gangs = GangInfo.build(np.array([4]))
    a, st, _ = gang_assign(state, pods, cfg(), gangs)
    assert (np.asarray(a)[:4] == -1).all()
    # and its capacity was fully returned
    assert int(st.node_requested[0, CPU]) == 0


def test_gang_min_member_below_total():
    # 4 pods, minMember 3, capacity for exactly 3 -> gang succeeds with 3
    state = mk_state([9_000])
    pods = mk_pods([3_000] * 4, [0, 0, 0, 0], state)
    gangs = GangInfo.build(np.array([3]))
    a, _, _ = gang_assign(state, pods, cfg(), gangs)
    assert (np.asarray(a)[:4] >= 0).sum() == 3


def test_failed_gang_frees_capacity_for_others():
    # gang needs 4x3000 on one 10k node (impossible); a lone pod needs 9000.
    # pass 1: gang pods grab capacity, lone pod may not fit; after rollback,
    # pass 2 must place the lone pod.
    state = mk_state([10_000])
    pods = mk_pods(
        [3_000, 3_000, 3_000, 3_000, 9_000],
        [0, 0, 0, 0, -1],
        state,
        priority=[9_500, 9_500, 9_500, 9_500, 3_000],  # gang first
    )
    gangs = GangInfo.build(np.array([4]))
    a, st, _ = gang_assign(state, pods, cfg(), gangs, passes=2)
    a = np.asarray(a)
    assert (a[:4] == -1).all()
    assert a[4] == 0
    assert int(st.node_requested[0, CPU]) == 9_000


def test_gang_group_all_or_nothing():
    # two gangs in one group; gang B cannot fit -> gang A rolls back too
    state = mk_state([4_000, 4_000])
    pods = mk_pods(
        [2_000, 2_000, 6_000, 6_000],
        [0, 0, 1, 1],
        state,
    )
    gangs = GangInfo.build(np.array([2, 2]), group_id=np.array([0, 0]))
    a, st, _ = gang_assign(state, pods, cfg(), gangs)
    assert (np.asarray(a)[:4] == -1).all()
    assert int(np.asarray(st.node_requested)[:, CPU].sum()) == 0

    # independent groups: gang A succeeds alone
    gangs2 = GangInfo.build(np.array([2, 2]), group_id=np.array([0, 1]))
    a2, _, _ = gang_assign(state, pods, cfg(), gangs2)
    assert (np.asarray(a2)[:2] >= 0).all()
    assert (np.asarray(a2)[2:4] == -1).all()


def test_pre_enqueue_blocks_incomplete_gang():
    state = mk_state([10_000])
    # gang 0 declares minMember 3 but only 2 pods are pending
    pods = mk_pods([1_000, 1_000], [0, 0], state)
    gangs = GangInfo.build(np.array([3]))
    mask = np.asarray(pre_enqueue_mask(pods, gangs))
    assert not mask[:2].any()
    a, _, _ = gang_assign(state, pods, cfg(), gangs)
    assert (np.asarray(a)[:2] == -1).all()


def test_surplus_member_of_satisfied_gang_binds_in_later_pass():
    # Gang A (3x2000, minMember 2) and higher-priority gang B (2x6000,
    # minMember 2) on one 10k node. Pass 1: B takes 12000? no - only one B pod
    # fits (6000+2000*2=10000), B fails, A keeps 2. Pass 2: A's third pod must
    # bind into B's freed capacity — the gang is already satisfied, so the
    # recount must credit A's prior keeps (Permit: satisfied gang binds more).
    state = mk_state([10_000])
    pods = mk_pods(
        [2_000, 2_000, 2_000, 6_000, 6_000],
        [0, 0, 0, 1, 1],
        state,
        priority=[5_000, 5_000, 5_000, 9_500, 9_500],
    )
    gangs = GangInfo.build(np.array([2, 2]))
    a, st, _ = gang_assign(state, pods, cfg(), gangs, passes=2)
    a = np.asarray(a)
    assert (a[:3] >= 0).all(), a  # all three A pods placed across passes
    assert (a[3:5] == -1).all()
    assert int(st.node_requested[0, CPU]) == 6_000


def test_multi_pass_respects_usage_threshold_feedback():
    # Regression: pass 2 must see pass-1 keeps' estimated usage. One node,
    # usage 5000/10000, threshold 65% (limit 6500), two 1000m pods: single
    # pass rejects the second (7000 > 6500); multi-pass must agree.
    alloc = np.zeros((1, R), np.int32)
    alloc[0, CPU], alloc[0, MEM] = 10_000, 100_000
    usage = np.zeros((1, R), np.int32)
    usage[0, CPU] = 5_000
    state = ClusterState.from_arrays(alloc, usage=usage)
    pods = mk_pods([1_000, 1_000], [-1, -1], state, mem=16)
    gangs = GangInfo.build(np.array([], dtype=np.int64).reshape(0))
    c = cfg().replace(usage_thresholds=jnp.zeros(R, jnp.int32).at[CPU].set(65))
    a, _, _ = gang_assign(state, pods, c, gangs, passes=2)
    from koordinator_tpu.ops.assignment import greedy_assign

    a1, _, _ = greedy_assign(state, pods, c)
    assert np.asarray(a)[:2].tolist() == np.asarray(a1)[:2].tolist() == [0, -1]


def test_gang_with_quota_rollback_restores_headroom():
    from koordinator_tpu.quota import QuotaDeviceState, QuotaTree
    from koordinator_tpu.quota.tree import UNBOUNDED

    state = mk_state([10_000])

    def vec(c, m):
        v = np.zeros(R, np.int64)
        v[CPU], v[MEM] = c, m
        return v

    mx = np.full(R, UNBOUNDED, np.int64)
    mx[CPU], mx[MEM] = 20_000, 131_072
    t = QuotaTree(vec(20_000, 131_072))
    t.add("q", min=vec(0, 0), max=mx)
    t.set_request("q", vec(12_000, 4_096))
    t.refresh_runtime()
    qs, idx = QuotaDeviceState.from_tree(t)
    before = int(qs.headroom[idx["q"], CPU])

    req = np.zeros((4, R), np.int32)
    req[:, CPU] = 3_000
    req[:, MEM] = 1_024
    pods = PodBatch.build(
        req,
        gang_id=np.zeros(4, np.int32),
        quota_id=np.full(4, idx["q"], np.int32),
        node_capacity=state.capacity,
    )
    gangs = GangInfo.build(np.array([4]))
    # node fits only 3 -> gang fails -> quota must be fully restored
    a, _, qs2 = gang_assign(state, pods, cfg(), gangs, quota=qs)
    assert (np.asarray(a)[:4] == -1).all()
    assert int(qs2.headroom[idx["q"], CPU]) == before


# ---- batch-parallel solver engine (gang_assign solver="batch") -------------

def test_gang_all_or_nothing_with_batch_solver():
    # 4-member gang, capacity for only 3: the batch engine must roll the
    # whole gang back exactly like the greedy engine
    state = mk_state([4_000, 4_000, 4_000])
    pods = mk_pods([3_000] * 4, [0, 0, 0, 0], state)
    gangs = GangInfo.build(np.array([4]))
    for solver in ("greedy", "batch"):
        a, new_state, _ = gang_assign(state, pods, cfg(), gangs,
                                      solver=solver)
        assert np.asarray(a)[:4].tolist() == [-1, -1, -1, -1], solver
        np.testing.assert_array_equal(
            np.asarray(new_state.node_requested),
            np.asarray(state.node_requested), err_msg=solver)


def test_gang_satisfied_with_batch_solver():
    state = mk_state([8_000] * 4)
    pods = mk_pods([2_000] * 3, [0, 0, 0], state)
    gangs = GangInfo.build(np.array([3]))
    a, _, _ = gang_assign(state, pods, cfg(), gangs, solver="batch")
    a = np.asarray(a)
    assert (a[:3] >= 0).all()


def test_gang_assign_rejects_unknown_solver():
    import pytest

    state = mk_state([8_000])
    pods = mk_pods([100], [0], state)
    with pytest.raises(ValueError, match="solver"):
        gang_assign(state, pods, cfg(), GangInfo.build(np.array([1])),
                    solver="annealing")


# -- the pruned scan under the gang pass loop (tests/scan_reference.py) -------


def stepwise_greedy_assign(state, pods, cfg, quota=None, with_grants=False):
    """``greedy_assign`` over the one-step-per-row reference scan."""
    from koordinator_tpu.ops.assignment import ScanStats
    from tests.scan_reference import scan_reference

    a, _, new_state, _, new_quota, grants, _ = scan_reference(
        state, pods, cfg, quota=quota)
    assert with_grants
    return a, new_state, new_quota, grants, ScanStats(steps=jnp.int32(0))


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("passes", [1, 2])
def test_gang_passes_over_the_pruned_scan_equal_the_stepwise_scan(
        passes, seed, monkeypatch):
    """Gangs that fit, a gang that is rolled back (its capacity goes to
    later pods in pass 2), dead rows and padded rows: every output of the
    pass loop equals the loop over the stepwise scan, and the step count
    is the sum of the passes' live rows."""
    from koordinator_tpu.ops import gang as gang_mod

    rng = np.random.default_rng(seed)
    state = mk_state(rng.integers(8_000, 16_000, 6))
    n = 40
    cpus = rng.integers(500, 3_000, n)
    gang_id = np.full(n, -1, np.int32)
    gang_id[:6], gang_id[6:14], gang_id[14:18] = 0, 1, 2
    cpus[6:14] = 7_000                  # gang 1: eight of these never fit
    dead = np.zeros(n, bool)
    dead[20:] = rng.random(n - 20) < 0.4
    cpus[dead] = 100_000
    pods = mk_pods(cpus, gang_id, state,
                   priority=rng.integers(5_000, 5_003, n))
    gangs = GangInfo.build(np.array([6, 8, 4], np.int32))
    solve = jax.jit(gang_assign, static_argnames=("passes", "with_grants"))
    a, st, _, grants, stats = solve(state, pods, cfg(), gangs,
                                    passes=passes, with_grants=True)
    monkeypatch.setattr(gang_mod, "greedy_assign", stepwise_greedy_assign)
    want_a, want_st, _, _, _ = jax.jit(
        gang_assign, static_argnames=("passes", "with_grants"))(
        state, pods, cfg(), gangs, passes=passes, with_grants=True)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(want_a))
    np.testing.assert_array_equal(np.asarray(st.node_requested),
                                  np.asarray(want_st.node_requested))
    a = np.asarray(a)[:n]
    assert grants is None
    assert (a[6:14] == -1).all() and (a[dead] == -1).all()
    live = n - int(dead.sum())
    assert int((a >= 0).sum()) <= int(stats.steps) <= passes * live
    assert int(stats.steps) >= live       # pass 1 steps every live row


def test_batch_engine_reports_no_scan():
    state = mk_state([10_000])
    pods = mk_pods([3_000] * 4, [0] * 4, state)
    gangs = GangInfo.build(np.array([4]))
    out = gang_assign(state, pods, cfg(), gangs, solver="batch",
                      with_grants=True)
    assert len(out) == 5 and out[4] is None
