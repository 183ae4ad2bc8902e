import jax
import jax.numpy as jnp
import numpy as np
import pytest

from koordinator_tpu.api.resources import NUM_RESOURCE_DIMS, ResourceDim
from koordinator_tpu.ops import assignment, filtering
from koordinator_tpu.ops.assignment import ScoringConfig, greedy_assign, score_pods
from koordinator_tpu.state.cluster_state import ClusterState, PodBatch
from tests.problem_helpers import build_problem
from tests.scan_reference import compare_with_reference

R = NUM_RESOURCE_DIMS
CPU, MEM = ResourceDim.CPU, ResourceDim.MEMORY


def mk_nodes(*cpu_mem):
    alloc = np.zeros((len(cpu_mem), R), np.int32)
    for i, (c, m) in enumerate(cpu_mem):
        alloc[i, CPU], alloc[i, MEM] = c, m
    return alloc


def mk_pods(*cpu_mem, priority=None):
    req = np.zeros((len(cpu_mem), R), np.int32)
    for i, (c, m) in enumerate(cpu_mem):
        req[i, CPU], req[i, MEM] = c, m
    prio = np.asarray(priority, np.int32) if priority is not None else None
    return req, prio


def plain_config():
    """Config with thresholds/estimator defaults off, for pure packing tests."""
    cfg = ScoringConfig.default()
    return cfg.replace(
        usage_thresholds=jnp.zeros(R, jnp.int32),
        estimator_defaults=jnp.zeros(R, jnp.int32),
        estimator_factors=jnp.full(R, 100, jnp.int32),
    )


def test_score_pods_prefers_emptier_node():
    alloc = mk_nodes((10_000, 32_768), (10_000, 32_768))
    requested = np.zeros((2, R), np.int32)
    requested[0, CPU] = 8_000  # node 0 heavily requested
    usage = np.zeros((2, R), np.int32)
    usage[0, CPU] = 7_000
    state = ClusterState.from_arrays(alloc, requested=requested, usage=usage)
    req, _ = mk_pods((1_000, 1_024))
    pods = PodBatch.build(req, node_capacity=state.capacity)
    scores, feasible = jax.jit(score_pods)(state, pods, plain_config())
    s = np.asarray(scores)[0]
    f = np.asarray(feasible)[0]
    assert f[0] and f[1]
    assert s[1] > s[0]


def test_score_pods_filters_full_and_invalid_nodes():
    alloc = mk_nodes((2_000, 4_096), (10_000, 32_768))
    requested = np.zeros((2, R), np.int32)
    requested[0, CPU] = 1_500
    state = ClusterState.from_arrays(alloc, requested=requested)
    req, _ = mk_pods((1_000, 1_024))
    pods = PodBatch.build(req, node_capacity=state.capacity)
    _, feasible = score_pods(state, pods, plain_config())
    f = np.asarray(feasible)[0]
    assert not f[0]          # only 500 mcpu free
    assert f[1]
    assert not f[2:].any()   # padded nodes are invalid


def test_greedy_assign_capacity_feedback():
    # Two pods that each fit either node but not together on one.
    alloc = mk_nodes((1_000, 4_096), (1_000, 4_096))
    state = ClusterState.from_arrays(alloc)
    req, _ = mk_pods((700, 1_024), (700, 1_024))
    pods = PodBatch.build(req, node_capacity=state.capacity)
    assignments, new_state, _ = jax.jit(greedy_assign)(state, pods, plain_config())
    a = np.asarray(assignments)[:2]
    assert set(a.tolist()) == {0, 1}
    assert np.asarray(new_state.node_requested)[:2, CPU].tolist() == [700, 700]


def test_greedy_assign_priority_order():
    # One good (empty) node, one loaded node: higher-priority pod should get
    # first pick even though it comes later in the batch.
    alloc = mk_nodes((10_000, 32_768), (10_000, 32_768))
    usage = np.zeros((2, R), np.int32)
    usage[0, CPU] = 6_000
    state = ClusterState.from_arrays(alloc, usage=usage)
    req, prio = mk_pods((9_000, 1_024), (9_000, 1_024), priority=[5500, 9500])
    pods = PodBatch.build(req, priority=prio, node_capacity=state.capacity)
    assignments, _, _ = greedy_assign(state, pods, plain_config())
    a = np.asarray(assignments)
    assert a[1] == 1  # prod pod got the emptier node
    assert a[0] == 0


def test_greedy_assign_unschedulable():
    alloc = mk_nodes((1_000, 1_024))
    state = ClusterState.from_arrays(alloc)
    req, _ = mk_pods((2_000, 512), (500, 512))
    pods = PodBatch.build(req, node_capacity=state.capacity)
    assignments, _, _ = greedy_assign(state, pods, plain_config())
    a = np.asarray(assignments)
    assert a[0] == -1
    assert a[1] == 0
    assert a[2:].tolist() == [-1] * (len(a) - 2)  # padded pods unassigned


def test_greedy_assign_respects_feasibility_mask():
    alloc = mk_nodes((10_000, 32_768), (10_000, 32_768))
    state = ClusterState.from_arrays(alloc)
    req, _ = mk_pods((1_000, 1_024))
    feasible = np.zeros((1, state.capacity), bool)
    feasible[0, 1] = True  # only node 1 allowed (e.g. nodeSelector)
    pods = PodBatch.build(req, feasible=feasible, node_capacity=state.capacity)
    assignments, _, _ = greedy_assign(state, pods, plain_config())
    assert int(assignments[0]) == 1


def test_greedy_assign_threshold_feedback():
    # LoadAware thresholds must apply to estimated usage accumulated during the
    # batch, not just the starting snapshot (assign-cache semantics).
    alloc = mk_nodes((1_000, 100_000))
    usage = np.zeros((1, R), np.int32)
    usage[0, CPU] = 400
    state = ClusterState.from_arrays(alloc, usage=usage)
    cfg = plain_config().replace(
        usage_thresholds=jnp.zeros(R, jnp.int32).at[CPU].set(65),
    )
    req, _ = mk_pods((200, 16), (200, 16))
    pods = PodBatch.build(req, node_capacity=state.capacity)
    assignments, _, _ = greedy_assign(state, pods, cfg)
    a = np.asarray(assignments)[:2]
    # First pod: 600/1000 = 60 <= 65 ok. Second: 800/1000 = 80 > 65 rejected.
    assert a[0] == 0
    assert a[1] == -1


def test_aggregated_thresholds_replace_instantaneous():
    # When aggregated (percentile) thresholds are configured they are checked
    # INSTEAD of the instantaneous ones (load_aware.go Filter either/or).
    alloc = mk_nodes((1_000, 100_000))
    usage = np.zeros((1, R), np.int32)
    usage[0, CPU] = 900          # instantaneous spike: 90%
    agg = np.zeros((1, R), np.int32)
    agg[0, CPU] = 300            # p95 usage: 30%
    state = ClusterState.from_arrays(alloc, usage=usage, agg_usage=agg)
    req, _ = mk_pods((50, 16))
    pods = PodBatch.build(req, node_capacity=state.capacity)

    base = plain_config()
    inst_only = base.replace(
        usage_thresholds=jnp.zeros(R, jnp.int32).at[CPU].set(65))
    _, feas = score_pods(state, pods, inst_only)
    assert not bool(np.asarray(feas)[0, 0])  # 95% > 65 -> rejected

    both = inst_only.replace(
        agg_usage_thresholds=jnp.zeros(R, jnp.int32).at[CPU].set(65))
    _, feas = score_pods(state, pods, both)
    assert bool(np.asarray(feas)[0, 0])  # agg policy replaces inst: 35% <= 65


def test_greedy_assign_deterministic():
    rng = np.random.default_rng(7)
    alloc = np.zeros((16, R), np.int32)
    alloc[:, CPU] = rng.integers(4_000, 16_000, 16)
    alloc[:, MEM] = rng.integers(8_192, 65_536, 16)
    state = ClusterState.from_arrays(alloc)
    req = np.zeros((32, R), np.int32)
    req[:, CPU] = rng.integers(100, 2_000, 32)
    req[:, MEM] = rng.integers(128, 4_096, 32)
    prio = rng.integers(3000, 9999, 32).astype(np.int32)
    pods = PodBatch.build(req, priority=prio, node_capacity=state.capacity)
    cfg = plain_config()
    a1, _, _ = greedy_assign(state, pods, cfg)
    a2, _, _ = greedy_assign(state, pods, cfg)
    assert np.array_equal(np.asarray(a1), np.asarray(a2))


# -- the scan steps only over the rows that are live at its entry -----------
# (tests/scan_reference.py: the one-step-per-padded-row scan it must equal)


def contended(seed, n_nodes=12, n_pods=300, dead_share=0.0,
              equal_priority=False, dense=False, capacity=None):
    """A seeded problem in which capacity runs out mid-scan: few nodes, many
    pods.  ``dead_share`` of the pods ask for more CPU than any node has."""
    state, pods = build_problem(n_nodes=n_nodes, n_pods=n_pods, seed=seed,
                                factored=not dense)
    rng = np.random.default_rng(1_000 + seed)
    requests = np.asarray(pods.requests).copy()
    dead = np.zeros(pods.capacity, bool)
    dead[:n_pods] = rng.random(n_pods) < dead_share
    requests[dead, CPU] = 1_000_000
    replaced = dict(requests=jnp.asarray(requests))
    if equal_priority:
        replaced["priority"] = jnp.full(pods.capacity, 5_000, jnp.int32)
    if dense:
        feasible = np.zeros((pods.capacity, n_nodes), bool)
        feasible[:n_pods] = rng.random((n_pods, n_nodes)) < 0.6
        replaced["feasible"] = jnp.asarray(feasible)
    pods = pods.replace(**replaced)
    if capacity is not None:        # an odd capacity: no power-of-two padding
        pods = jax.tree.map(lambda a: a[:capacity], pods)
    return state, pods, dead[:pods.capacity]


SCAN_CASES = {
    # every valid row has a node at entry; capacity still runs out mid-scan
    "no_dead_rows": dict(n_nodes=48, n_pods=60),
    "all_dead_rows": dict(dead_share=1.0),
    "mixed_with_padded_rows": dict(dead_share=0.4),
    "equal_priorities": dict(dead_share=0.3, equal_priority=True),
    "dense_feasibility": dict(dead_share=0.3, dense=True),
}


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("case", list(SCAN_CASES))
def test_pruned_scan_equals_the_stepwise_scan(case, seed):
    state, pods, dead = contended(seed, **SCAN_CASES[case])
    a, steps, alive, step_feasible = compare_with_reference(
        state, pods, ScoringConfig.default())
    valid = np.asarray(pods.valid)
    assert not alive[dead].any() and (a[dead] == -1).all()
    if case == "all_dead_rows":
        assert steps == 0 and (a == -1).all()
    elif case == "no_dead_rows":
        assert steps == int(valid.sum())
    else:
        # the scan is contended: rows live at entry lose their node mid-scan
        assert 0 < int((a >= 0).sum()) < steps < int(valid.sum())
        assert (alive & ~step_feasible).any()


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_entry_filter_in_blocks_with_a_ragged_tail(seed, monkeypatch):
    # 300 rows in blocks of 64: five blocks, the last re-reads row 299
    monkeypatch.setattr(assignment, "_ENTRY_BLOCK", 64)
    state, pods, dead = contended(seed, dead_share=0.4, capacity=300)
    assert pods.capacity == 300
    a, steps, alive, _ = compare_with_reference(
        state, pods, ScoringConfig.default(),
        scan=jax.jit(assignment._greedy_scan))
    assert 0 < steps == int(alive.sum()) < 300 and not alive[dead].any()


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_pruned_scan_with_devices_equals_the_stepwise_scan(seed):
    """``gpushare-1k``'s standing pods: the node's aggregate GPU rows hold
    the ask, no single device does.  Only the device term of the entry
    filter prunes them."""
    from tests.test_gpushare_deployment import ASKS, fragmented

    _, state = fragmented(seed)
    rng = np.random.default_rng(seed)
    names = list(ASKS)
    picks = rng.integers(len(names), size=96)
    request = np.zeros((96, R), np.int32)
    request[:, CPU] = 1_000
    for i, pick in enumerate(picks):
        request[i, ResourceDim.GPU], request[i, ResourceDim.GPU_MEMORY] = (
            ASKS[names[pick]])
    pods = PodBatch.build(request, class_capacity=8,
                          priority=rng.integers(9_000, 9_010, 96))
    a, steps, alive, _ = compare_with_reference(
        state, pods, ScoringConfig.default())
    too_wide = np.zeros(pods.capacity, bool)
    too_wide[:96] = picks == names.index("too_wide")
    aggregate_fits = np.asarray(filtering.fit_mask(
        state.free, pods.requests)).any(axis=1)
    assert too_wide.any() and aggregate_fits[too_wide].all()
    assert not alive[too_wide].any() and (a[too_wide] == -1).all()
    assert 0 < int((a >= 0).sum()) <= steps


def test_greedy_assign_reports_its_steps():
    state, pods, dead = contended(0, dead_share=0.4)
    solve = jax.jit(greedy_assign, static_argnames=("with_grants",))
    a, _, _, grants, stats = solve(state, pods, ScoringConfig.default(),
                                   with_grants=True)
    assert grants is None
    live = int(np.asarray(pods.valid).sum()) - int(dead.sum())
    assert int((np.asarray(a) >= 0).sum()) <= int(stats.steps) <= live
    assert len(solve(state, pods, ScoringConfig.default())) == 3
