import jax
import jax.numpy as jnp
import numpy as np
import pytest

from koordinator_tpu.api.resources import NUM_RESOURCE_DIMS, ResourceDim
from koordinator_tpu.ops.assignment import ScoringConfig, greedy_assign
from koordinator_tpu.quota import (
    QuotaDeviceState,
    QuotaTree,
    charge_quota,
    quota_admission_mask,
)
from koordinator_tpu.quota.tree import UNBOUNDED, hamilton_deltas
from koordinator_tpu.state.cluster_state import ClusterState, PodBatch
from tests.scan_reference import compare_with_reference

R = NUM_RESOURCE_DIMS
CPU, MEM = ResourceDim.CPU, ResourceDim.MEMORY


def vec(cpu=0, mem=0, fill=0):
    v = np.full(R, fill, dtype=np.int64)
    v[CPU], v[MEM] = cpu, mem
    return v


def unbounded(cpu=None, mem=None):
    v = np.full(R, UNBOUNDED, dtype=np.int64)
    if cpu is not None:
        v[CPU] = cpu
    if mem is not None:
        v[MEM] = mem
    return v


# -- Hamilton apportionment -------------------------------------------------


def test_hamilton_exact_split():
    assert hamilton_deltas(100, 4, [1, 3], ["a", "b"]) == [25, 75]


def test_hamilton_residual_largest_remainder():
    # 100 over weights 1,1,1: base 33 each, residual 1 -> largest remainder
    # (all equal) -> name asc tie-break gives "a" the extra.
    assert hamilton_deltas(100, 3, [1, 1, 1], ["a", "b", "c"]) == [34, 33, 33]
    # remainders 2/3,2/3,2/3 after base... verify conservation always:
    for pool, ws in ((7, [2, 3, 5]), (11, [1, 7, 3]), (1, [9, 9])):
        d = hamilton_deltas(pool, sum(ws), ws, [str(i) for i in range(len(ws))])
        assert sum(d) == pool


def test_hamilton_zero_weight_gets_nothing():
    assert hamilton_deltas(10, 5, [5, 0], ["a", "b"]) == [10, 0]


def test_hamilton_huge_values_exact():
    # the reference needs 128-bit here; python ints are exact
    pool = 2**40
    ws = [2**35, 2**35 + 1]
    d = hamilton_deltas(pool, sum(ws), ws, ["a", "b"])
    assert sum(d) == pool


# -- redistribution ---------------------------------------------------------


def test_redistribution_min_then_fair_share():
    t = QuotaTree(vec(100))
    t.add("a", min=vec(10), max=unbounded(cpu=1000))
    t.add("b", min=vec(20), max=unbounded(cpu=1000))
    # equal shared weights
    t.nodes["a"].shared_weight = vec(1)
    t.nodes["b"].shared_weight = vec(1)
    t.set_request("a", vec(60))
    t.set_request("b", vec(60))
    t.refresh_runtime()
    # start at min (10, 20), pool 70 split 35/35 -> 45/55, both < request
    assert t.runtime_of("a")[CPU] == 45
    assert t.runtime_of("b")[CPU] == 55


def test_redistribution_saturation_waterfill():
    t = QuotaTree(vec(100))
    t.add("a", min=vec(0), max=unbounded(cpu=1000))
    t.add("b", min=vec(0), max=unbounded(cpu=1000))
    t.nodes["a"].shared_weight = vec(1)
    t.nodes["b"].shared_weight = vec(1)
    t.set_request("a", vec(30))
    t.set_request("b", vec(200))
    t.refresh_runtime()
    # round 1: 50/50, a saturates at 30 returning 20; round 2: b gets 70
    assert t.runtime_of("a")[CPU] == 30
    assert t.runtime_of("b")[CPU] == 70


def test_redistribution_no_lent_keeps_min():
    t = QuotaTree(vec(100))
    t.add("a", min=vec(40), max=unbounded(cpu=1000), allow_lent=False)
    t.add("b", min=vec(0), max=unbounded(cpu=1000))
    t.nodes["a"].shared_weight = vec(1)
    t.nodes["b"].shared_weight = vec(1)
    t.set_request("a", vec(5))     # requests less than min but won't lend
    t.set_request("b", vec(500))
    t.refresh_runtime()
    assert t.runtime_of("a")[CPU] == 40   # keeps its min
    assert t.runtime_of("b")[CPU] == 60


def test_redistribution_guarantee_overrides_min():
    t = QuotaTree(vec(100))
    t.add("a", min=vec(10), max=unbounded(cpu=1000), guarantee=vec(30))
    t.add("b", min=vec(0), max=unbounded(cpu=1000))
    t.nodes["a"].shared_weight = vec(1)
    t.nodes["b"].shared_weight = vec(1)
    t.set_request("a", vec(100))
    t.set_request("b", vec(100))
    t.refresh_runtime()
    # a starts at guarantee 30, pool 70 split 35/35 -> a=65, b=35
    assert t.runtime_of("a")[CPU] == 65
    assert t.runtime_of("b")[CPU] == 35


def test_redistribution_request_capped_by_max():
    t = QuotaTree(vec(100))
    t.add("a", min=vec(0), max=unbounded(cpu=25))
    t.add("b", min=vec(0), max=unbounded(cpu=1000))
    t.nodes["a"].shared_weight = vec(1)
    t.nodes["b"].shared_weight = vec(1)
    t.set_request("a", vec(80))   # limited to max 25
    t.set_request("b", vec(80))
    t.refresh_runtime()
    assert t.runtime_of("a")[CPU] == 25
    assert t.runtime_of("b")[CPU] == 75


def test_hierarchical_redistribution():
    t = QuotaTree(vec(100))
    t.add("parent", min=vec(0), max=unbounded(cpu=1000))
    t.add("other", min=vec(0), max=unbounded(cpu=1000))
    t.add("c1", min=vec(0), max=unbounded(cpu=1000), parent="parent")
    t.add("c2", min=vec(0), max=unbounded(cpu=1000), parent="parent")
    for n in t.nodes.values():
        n.shared_weight = vec(1)
    t.set_request("c1", vec(40))
    t.set_request("c2", vec(40))
    t.set_request("other", vec(20))
    t.refresh_runtime()
    # parent aggregates 80, other 20; exactly satisfiable
    assert t.runtime_of("parent")[CPU] == 80
    assert t.runtime_of("other")[CPU] == 20
    assert t.runtime_of("c1")[CPU] == 40
    assert t.runtime_of("c2")[CPU] == 40


# -- device admission -------------------------------------------------------


def build_device(tree, **kw):
    state, index = QuotaDeviceState.from_tree(tree, **kw)
    return state, index


def test_admission_basic_and_parent_chain():
    t = QuotaTree(vec(100, 1000))
    t.add("team", min=vec(0), max=unbounded(cpu=50, mem=500))
    t.add("app", min=vec(0), max=unbounded(cpu=40, mem=400), parent="team")
    t.add("app2", min=vec(0), max=unbounded(cpu=40, mem=400), parent="team")
    t.set_request("app", vec(40, 400))
    t.set_request("app2", vec(40, 400))
    t.refresh_runtime()
    # team aggregates 80 capped at max 50 -> runtime 50, split 25/25 to apps
    assert t.runtime_of("team")[CPU] == 50
    assert t.runtime_of("app")[CPU] == 25
    t.set_used("team", vec(45, 0))   # team nearly exhausted on cpu
    t.set_used("app", vec(10, 0))
    qs, idx = build_device(t)

    req = np.zeros((2, R), np.int32)
    req[0, CPU] = 4   # team headroom 5 left: fits
    req[1, CPU] = 6   # exceeds team (parent) headroom 5, fits app's own 15
    qid = np.full(2, idx["app"], np.int32)
    mask = np.asarray(
        quota_admission_mask(qs, jnp.asarray(req), jnp.asarray(qid))
    )
    assert mask.tolist() == [True, False]

    # without parent checking the second pod is admitted (app headroom 30)
    mask2 = np.asarray(
        quota_admission_mask(
            qs, jnp.asarray(req), jnp.asarray(qid), check_parents=False
        )
    )
    assert mask2.tolist() == [True, True]


def test_admission_no_quota_pod_always_admitted():
    t = QuotaTree(vec(10))
    t.add("q", min=vec(0), max=unbounded(cpu=1))
    t.refresh_runtime()
    qs, _ = build_device(t)
    req = np.zeros((1, R), np.int32)
    req[0, CPU] = 999
    mask = quota_admission_mask(
        qs, jnp.asarray(req), jnp.asarray(np.array([-1], np.int32))
    )
    assert bool(mask[0])


def test_admission_unbounded_dims_unchecked():
    t = QuotaTree(vec(100, 1000))
    t.add("q", min=vec(0), max=unbounded(cpu=50))  # memory unbounded
    t.set_request("q", vec(50, 0))
    t.refresh_runtime()
    qs, idx = build_device(t)
    req = np.zeros((1, R), np.int32)
    req[0, CPU] = 10
    req[0, MEM] = 10**6  # huge but unchecked dim
    mask = quota_admission_mask(
        qs, jnp.asarray(req), jnp.asarray(np.array([idx["q"]], np.int32))
    )
    assert bool(mask[0])


def test_admission_non_preemptible_checks_min():
    t = QuotaTree(vec(100))
    t.add("q", min=vec(10), max=unbounded(cpu=50))
    t.set_request("q", vec(50))
    t.refresh_runtime()
    t.set_used("q", vec(0), non_preemptible=vec(8))
    qs, idx = build_device(t)
    req = np.zeros((2, R), np.int32)
    req[0, CPU] = 2    # 8+2 <= min 10
    req[1, CPU] = 3    # 8+3 > min 10
    qid = np.full(2, idx["q"], np.int32)
    np_flag = jnp.asarray(np.array([True, True]))
    mask = np.asarray(
        quota_admission_mask(qs, jnp.asarray(req), jnp.asarray(qid), np_flag)
    )
    assert mask.tolist() == [True, False]


def test_charge_quota_feedback():
    t = QuotaTree(vec(100))
    t.add("team", min=vec(0), max=unbounded(cpu=50))
    t.add("app", min=vec(0), max=unbounded(cpu=50), parent="team")
    t.set_request("app", vec(50))
    t.refresh_runtime()
    qs, idx = build_device(t)
    req = np.zeros(R, np.int32)
    req[CPU] = 30
    qs2 = charge_quota(qs, jnp.asarray(req), jnp.asarray(idx["app"]))
    # both app and team headroom drop by 30
    assert int(qs2.headroom[idx["app"], CPU]) == int(qs.headroom[idx["app"], CPU]) - 30
    assert int(qs2.headroom[idx["team"], CPU]) == int(qs.headroom[idx["team"], CPU]) - 30
    # uncharge restores
    qs3 = charge_quota(qs2, jnp.asarray(req), jnp.asarray(idx["app"]), sign=-1)
    assert np.array_equal(np.asarray(qs3.headroom), np.asarray(qs.headroom))


def test_admission_stale_quota_id_rejected():
    # a quota_id pointing at a padded/invalid row must reject, not admit
    t = QuotaTree(vec(10))
    t.add("q", min=vec(0), max=unbounded(cpu=5))
    t.refresh_runtime()
    qs, _ = build_device(t)
    req = np.zeros((1, R), np.int32)
    req[0, CPU] = 1
    stale = qs.capacity - 1  # padded row
    mask = quota_admission_mask(
        qs, jnp.asarray(req), jnp.asarray(np.array([stale], np.int32))
    )
    assert not bool(mask[0])


def test_admission_checked_dims_follow_pods_quota():
    # ancestor leaves CPU unbounded but is over-used; the pod's own quota
    # declares CPU, so the reference still checks CPU at the ancestor.
    t = QuotaTree(vec(100))
    t.add("team", min=vec(0), max=np.full(R, UNBOUNDED, np.int64))  # no caps
    t.add("app", min=vec(0), max=unbounded(cpu=40), parent="team")
    t.set_request("app", vec(40))
    t.refresh_runtime()
    # runtime caps at aggregated requests: team runtime == app runtime == 40
    t.set_used("team", vec(36))
    qs, idx = build_device(t)
    req = np.zeros((1, R), np.int32)
    req[0, CPU] = 3  # app headroom 40, team headroom 40-36=4 -> fits
    ok = quota_admission_mask(
        qs, jnp.asarray(req), jnp.asarray(np.array([idx["app"]], np.int32))
    )
    assert bool(ok[0])
    t.set_used("team", vec(39))  # team headroom 1 on its unbounded dim
    qs2, _ = build_device(t)
    ok2 = quota_admission_mask(
        qs2, jnp.asarray(req), jnp.asarray(np.array([idx["app"]], np.int32))
    )
    assert not bool(ok2[0])  # CPU is in app's max -> checked at team too


def test_charge_quota_non_preemptible_updates_min_headroom():
    t = QuotaTree(vec(100))
    t.add("q", min=vec(10), max=unbounded(cpu=50))
    t.set_request("q", vec(50))
    t.refresh_runtime()
    qs, idx = build_device(t)
    req = np.zeros(R, np.int32)
    req[CPU] = 8
    qs2 = charge_quota(qs, jnp.asarray(req), jnp.asarray(idx["q"]),
                       non_preemptible=True)
    assert int(qs2.min_headroom[idx["q"], CPU]) == 2
    # a second 8-core non-preemptible pod must now fail the min check
    mask = quota_admission_mask(
        qs2, jnp.asarray(req[None, :]), jnp.asarray(np.array([idx["q"]], np.int32)),
        jnp.asarray(np.array([True])),
    )
    assert not bool(mask[0])


# -- greedy integration -----------------------------------------------------


def test_greedy_assign_respects_quota():
    alloc = np.zeros((2, R), np.int32)
    alloc[:, CPU] = 10_000
    alloc[:, MEM] = 65_536
    state = ClusterState.from_arrays(alloc)

    t = QuotaTree(vec(20_000, 131_072))
    t.add("q", min=vec(0), max=unbounded(cpu=1_500, mem=131_072))
    t.set_request("q", vec(2_000, 2_048))
    t.refresh_runtime()
    qs, idx = build_device(t)

    req = np.zeros((2, R), np.int32)
    req[:, CPU] = 1_000
    req[:, MEM] = 1_024
    pods = PodBatch.build(
        req,
        quota_id=np.full(2, idx["q"], np.int32),
        node_capacity=state.capacity,
    )
    cfg = ScoringConfig.default().replace(
        usage_thresholds=jnp.zeros(R, jnp.int32),
        estimator_defaults=jnp.zeros(R, jnp.int32),
    )
    a, _, qs2 = jax.jit(greedy_assign)(state, pods, cfg, qs)
    a = np.asarray(a)[:2]
    # quota runtime = 1500 cpu: only one 1000m pod admitted
    assert sorted(a.tolist())[0] == -1
    assert sorted(a.tolist())[1] >= 0
    assert int(qs2.headroom[idx["q"], CPU]) == 500


# -- the pruned scan under quota (tests/scan_reference.py) -------------------


def leaves_under_a_parent(leaf_cpu, parent_cpu, min_cpu=None):
    """A flattened tree built by hand: row 0 the parent, rows 1.. its
    leaves, CPU the one checked dim, headrooms as given."""
    q, cap, depth = len(leaf_cpu) + 1, 8, 4
    headroom = np.zeros((cap, R), np.int32)
    headroom[0, CPU], headroom[1:q, CPU] = parent_cpu, leaf_cpu
    min_headroom = headroom.copy()
    if min_cpu is not None:
        min_headroom[1:q, CPU] = min_cpu
    checked = np.zeros((cap, R), bool)
    checked[:q, CPU] = True
    chain = np.full((cap, depth), -1, np.int32)
    chain[0, 0] = 0
    chain[1:q, 0], chain[1:q, 1] = np.arange(1, q), 0
    valid = np.zeros(cap, bool)
    valid[:q] = True
    return QuotaDeviceState(
        headroom=jnp.asarray(headroom), min_headroom=jnp.asarray(min_headroom),
        checked=jnp.asarray(checked), chain=jnp.asarray(chain),
        valid=jnp.asarray(valid))


QUOTA_SCAN_CASES = {
    # leaf 1 is at its max when the scan starts: its pods are dead though
    # every node has room; the other leaves never bind
    "leaf_at_its_max_at_entry": dict(leaf_cpu=[0, 900_000, 900_000],
                                     parent_cpu=2_000_000),
    # every pod is admitted at entry; leaf 1 runs out after a few
    "leaf_exhausted_mid_scan": dict(leaf_cpu=[5_000, 900_000, 900_000],
                                    parent_cpu=2_000_000),
    # the leaves have room, their parent runs out mid-scan
    "parent_exhausted_mid_scan": dict(leaf_cpu=[900_000] * 3,
                                      parent_cpu=20_000),
    # non-preemptible pods check min besides: leaf 1's min is spent at
    # entry, leaf 2's mid-scan
    "min_spent_for_non_preemptible": dict(leaf_cpu=[900_000] * 3,
                                          parent_cpu=2_000_000,
                                          min_cpu=[0, 6_000, 900_000]),
}


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("case", list(QUOTA_SCAN_CASES))
def test_pruned_scan_under_quota_equals_the_stepwise_scan(case, seed):
    rng = np.random.default_rng(seed)
    alloc = np.zeros((16, R), np.int32)
    alloc[:, CPU], alloc[:, MEM] = 64_000, 262_144
    state = ClusterState.from_arrays(alloc)
    n = 40
    req = np.zeros((n, R), np.int32)
    req[:, CPU] = rng.integers(500, 2_500, n)
    req[:, MEM] = 512
    quota_id = rng.integers(1, 4, n).astype(np.int32)
    quota_id[rng.random(n) < 0.1] = -1          # no quota: always admitted
    kw = QUOTA_SCAN_CASES[case]
    pods = PodBatch.build(
        req, priority=rng.integers(5_000, 5_004, n).astype(np.int32),
        quota_id=quota_id, node_capacity=state.capacity,
        non_preemptible=(rng.random(n) < 0.5 if "min_cpu" in kw else None))
    qs = leaves_under_a_parent(**kw)
    a, steps, alive, step_feasible = compare_with_reference(
        state, pods, ScoringConfig.default(), quota=qs)
    a, dead = a[:n], quota_id == 1
    if case == "leaf_at_its_max_at_entry":
        assert steps == int((~dead).sum()) == int((a >= 0).sum())
    else:
        # some admitted at entry are turned away at their own step
        dead &= (np.asarray(pods.non_preemptible)[:n] if "min_cpu" in kw
                 else False)
        assert steps == int((~dead).sum())
        assert (alive & ~step_feasible).any()
        assert 0 < int((a >= 0).sum()) < steps
    assert not alive[:n][dead].any() and (a[dead] == -1).all()
