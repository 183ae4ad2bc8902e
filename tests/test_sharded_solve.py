"""Shard-count invariance of the node-axis shard_map solve (ISSUE 10).

The contract under test (parallel/sharded.py module docstring): for any
1/2/4/8-way nodes-axis mesh on the virtual 8-device CPU platform, the
sharded selection / propose-accept rounds / incremental dirty-node
refresh produce BIT-IDENTICAL assignments, node accounting and quota
charges to the single-device solver — and the >32,768-node wide
ranking-key regime composes with sharding (the old ceiling is gone).

Compile cost dominates on CPU, so the suite reuses ONE small problem and
sweeps shard counts inside each test (the jit caches persist across the
sweep's reference solves).
"""

import numpy as np
import pytest

from koordinator_tpu.api.resources import NUM_RESOURCE_DIMS, ResourceDim
from koordinator_tpu.ops import batch_assign as ba
from koordinator_tpu.ops.assignment import ScoringConfig
from koordinator_tpu.parallel import mesh as pmesh
from koordinator_tpu.parallel import sharded as ps
from koordinator_tpu.quota.admission import QuotaDeviceState
from koordinator_tpu.quota.tree import UNBOUNDED, QuotaTree
from koordinator_tpu.state.cluster_state import _bucket

from tests.test_mesh import build_problem

R = NUM_RESOURCE_DIMS
CPU = ResourceDim.CPU

SHARD_COUNTS = (1, 2, 4, 8)
#: the 1/2/4/8 sweeps keep programs small (single stratum, tiny k) —
#: compile count x4 dominates tier-1 cost; the stratified default is
#: covered once at mesh width in test_pass_pipeline_invariant
K, ROUNDS, SB = 4, 2, 5


def _mesh(d):
    import jax

    return pmesh.solver_mesh(jax.devices()[:d])


def _quota_fixture(pods):
    import jax.numpy as jnp

    total = np.zeros(R, np.int64)
    total[CPU] = 60_000
    tree = QuotaTree(total)
    mx = np.full(R, UNBOUNDED, np.int64)
    mx[CPU] = 24_000
    tree.add("q", min=np.zeros(R, np.int64), max=mx)
    tree.set_request("q", total)
    tree.refresh_runtime()
    # depth 3 (not the default 8): every unused ancestor level unrolls
    # another device-wide prefix-accept sort into the rounds program,
    # and compile time is this suite's tier-1 budget
    quota, index = QuotaDeviceState.from_tree(tree, max_depth=3)
    qid = np.full(pods.capacity, -1, np.int32)
    qid[4:20] = index["q"]
    return quota, pods.replace(quota_id=jnp.asarray(qid))


def test_selection_and_rounds_invariant_across_shard_counts():
    """select + quota-charged rounds: assignments, node accounting and
    quota headroom bit-identical at 1/2/4/8 shards."""
    state, pods = build_problem(n_nodes=64, n_pods=32)
    cfg = ScoringConfig.default()
    quota, pods = _quota_fixture(pods)
    ck, cn, cs = ba.select_candidates(state, pods, cfg, k=K,
                                      spread_bits=SB, method="exact",
                                      with_scores=True)
    a_ref, st_ref, q_ref = ba._assign_rounds(state, pods, quota, ck, cn,
                                             ROUNDS)
    valid = np.asarray(ck) >= 0
    for d in SHARD_COUNTS:
        mesh = _mesh(d)
        sck, scn, scs = ps.sharded_select_candidates(
            mesh, state, pods, cfg, k=K, spread_bits=SB,
            with_scores=True)
        np.testing.assert_array_equal(np.asarray(sck), np.asarray(ck),
                                      err_msg=f"keys d={d}")
        np.testing.assert_array_equal(
            np.asarray(scn)[valid], np.asarray(cn)[valid],
            err_msg=f"nodes d={d}")
        np.testing.assert_array_equal(
            np.asarray(scs)[valid], np.asarray(cs)[valid],
            err_msg=f"scores d={d}")
        if d == 1:
            # the single-device reference above IS the 1-device solve;
            # compiling a 1-way rounds program re-proves it at real
            # tier-1 cost (selection still exercises the 1-way
            # shard_map path)
            continue
        a, st, q = ps.sharded_assign_rounds(mesh, state, pods, quota,
                                            sck, scn, ROUNDS)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(a_ref),
                                      err_msg=f"assignments d={d}")
        np.testing.assert_array_equal(
            np.asarray(st.node_requested),
            np.asarray(st_ref.node_requested), err_msg=f"state d={d}")
        np.testing.assert_array_equal(
            np.asarray(q.headroom), np.asarray(q_ref.headroom),
            err_msg=f"quota d={d}")


def test_incremental_refresh_invariant_across_shard_counts():
    """The dirty-node refresh: a dirty node rescores only on its owning
    shard, yet the merged cache equals the single-device refresh and the
    post-refresh solve is bit-identical at every shard count."""
    import jax.numpy as jnp

    state, pods = build_problem(n_nodes=64, n_pods=32, seed=11)
    cfg = ScoringConfig.default()
    ck, cn, cs = ba.select_candidates(state, pods, cfg, k=K,
                                      spread_bits=SB, method="exact",
                                      with_scores=True)
    cache = ba.CandidateCache(ck, cn, cs)
    # ~1% of a real cluster collapses to one node here; dirty a couple of
    # rows spread across different shards of the 8-way split
    dirty = [3, 40]
    dpad = _bucket(len(dirty), minimum=64)
    drows = np.zeros(dpad, np.int32)
    drows[: len(dirty)] = dirty
    dvalid = np.zeros(dpad, bool)
    dvalid[: len(dirty)] = True
    st2 = state.replace(
        node_usage=state.node_usage.at[jnp.asarray(dirty)].set(0))
    rk_ref, rc_ref = ba.refresh_candidates(
        st2, pods, cfg, cache, jnp.asarray(drows), jnp.asarray(dvalid),
        k=K, spread_bits=SB)
    a_ref, st_ref, _ = ba._assign_rounds(st2, pods, None, rk_ref,
                                         rc_ref.cand_node, ROUNDS)
    valid = np.asarray(rk_ref) >= 0
    for d in SHARD_COUNTS:
        mesh = _mesh(d)
        rk, rc = ps.sharded_refresh_candidates(
            mesh, st2, pods, cfg, cache, jnp.asarray(drows),
            jnp.asarray(dvalid), k=K, spread_bits=SB)
        np.testing.assert_array_equal(np.asarray(rk), np.asarray(rk_ref),
                                      err_msg=f"refresh keys d={d}")
        np.testing.assert_array_equal(
            np.asarray(rc.cand_node)[valid],
            np.asarray(rc_ref.cand_node)[valid],
            err_msg=f"refresh nodes d={d}")
        np.testing.assert_array_equal(
            np.asarray(rc.cand_score)[valid],
            np.asarray(rc_ref.cand_score)[valid],
            err_msg=f"refresh scores d={d}")
        # assignments from the dirty path, per shard count: the merged
        # cache is bit-identical, so solving each d's refreshed
        # candidates through the (already compiled) single-device
        # rounds must land on the reference assignments — the
        # non-vacuous cross-check without a new rounds program per d
        a, _, _ = ba._assign_rounds(st2, pods, None, rk, rc.cand_node,
                                    ROUNDS)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(a_ref),
                                      err_msg=f"post-refresh d={d}")


def test_pass_pipeline_invariant_at_mesh_width():
    """assign_round_pass + assign_followup_pass (the scheduler's
    incremental pass loop) at the full 8-way mesh: est accumulation,
    followup re-selection and the commit accounting bit-identical."""
    state, pods = build_problem(n_nodes=64, n_pods=32, seed=7)
    cfg = ScoringConfig.default()
    # quota=None here: the quota-on-mesh parity (admission + prefix +
    # charges) is already pinned across shard counts by the rounds
    # sweep above, and the quota chain doubles these two programs'
    # compile cost — the pass loop's OWN semantics (est accumulation,
    # followup re-select against the augmented state, commit into the
    # un-augmented accounting) are what this test adds
    k, rounds = 8, 4            # the stratified (5, 15) default path
    ck, cn, _ = ba.select_candidates(state, pods, cfg, k=k,
                                     method="exact", with_scores=True)
    ref1 = ba.assign_round_pass(state, pods, None, ck, cn, cfg,
                                rounds=rounds)
    ref2 = ba.assign_followup_pass(state, ref1[3], pods, None, cfg,
                                   k=k, rounds=rounds, method="exact")
    mesh = _mesh(8)
    a1, st1, _, est1 = ps.sharded_assign_round_pass(
        mesh, state, pods, None, ck, cn, cfg, rounds=rounds)
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(ref1[0]))
    np.testing.assert_array_equal(np.asarray(st1.node_requested),
                                  np.asarray(ref1[1].node_requested))
    np.testing.assert_array_equal(np.asarray(est1), np.asarray(ref1[3]))
    a2, st2, _, est2 = ps.sharded_assign_followup_pass(
        mesh, state, est1, pods, None, cfg, k=k, rounds=rounds)
    np.testing.assert_array_equal(np.asarray(a2), np.asarray(ref2[0]))
    np.testing.assert_array_equal(np.asarray(st2.node_requested),
                                  np.asarray(ref2[1].node_requested))
    np.testing.assert_array_equal(np.asarray(est2), np.asarray(ref2[3]))


def test_wide_regime_breaks_the_old_ceiling():
    """A 65,536-node problem — double the old 32,768 wall — selects and
    solves, and the 2-way sharded solve matches bit-for-bit."""
    state, pods = build_problem(n_nodes=65_536, n_pods=8, seed=5)
    cfg = ScoringConfig.default()
    assert not ba._packed_regime(state.capacity)
    ck, cn = ba.select_candidates(state, pods, cfg, k=K, spread_bits=SB,
                                  method="exact")
    a_ref, st_ref, _ = ba._assign_rounds(state, pods, None, ck, cn,
                                         ROUNDS)
    assert int((np.asarray(a_ref) >= 0).sum()) == 8
    mesh = _mesh(2)
    sck, scn = ps.sharded_select_candidates(mesh, state, pods, cfg, k=K,
                                            spread_bits=SB)
    valid = np.asarray(ck) >= 0
    np.testing.assert_array_equal(np.asarray(sck), np.asarray(ck))
    np.testing.assert_array_equal(np.asarray(scn)[valid],
                                  np.asarray(cn)[valid])
    # identical candidates => identical rounds (the rounds are a pure
    # function of (state, pods, candidates); their 1/2/4/8 invariance is
    # proven at small shapes above — recompiling them at 65k columns
    # buys no new evidence and real tier-1 seconds)
    # no overcommit at the new scale
    assert (np.asarray(st_ref.node_requested)
            <= np.asarray(st_ref.node_allocatable)).all()


def test_wide_regime_rank_matches_lexicographic_oracle():
    """Wide-regime top-k == a NumPy (quantized score, tie-break)
    lexicographic sort oracle — the exactness anchor the packed-key
    regime has had since PR 1, restated past the 2**15 wall."""
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    p, n_total = 2, 40_000
    scores = rng.integers(0, 3_000, (p, n_total)).astype(np.int32)
    feasible = rng.random((p, n_total)) < 0.5
    sb = 5
    key, tb = ba._rank_parts(jnp.asarray(scores), jnp.asarray(feasible),
                             sb, jnp.arange(p, dtype=jnp.int32))
    kv, idx = ba._topk_by_rank(key, tb, 16, n_total)
    key_np, tb_np = np.asarray(key), np.asarray(tb)
    for i in range(p):
        order = np.lexsort((-tb_np[i], -key_np[i]))
        np.testing.assert_array_equal(np.asarray(idx)[i], order[:16],
                                      err_msg=f"row {i}")
        np.testing.assert_array_equal(np.asarray(kv)[i],
                                      key_np[i][order[:16]])


def test_check_node_capacity_new_ceiling():
    """The 32,768 wall is deleted; the loud guard moved to 2**30."""
    ba.check_node_capacity(40_960)            # the old failure shape
    ba.check_node_capacity(ba.MAX_NODE_CAPACITY)
    with pytest.raises(ValueError, match="ranking-key ceiling"):
        ba.check_node_capacity(ba.MAX_NODE_CAPACITY + 1)


def test_capacity_must_divide_over_the_mesh():
    state, pods = build_problem(n_nodes=60, n_pods=8)
    cfg = ScoringConfig.default()
    with pytest.raises(ValueError, match="does not divide"):
        ps.sharded_select_candidates(_mesh(8), state, pods, cfg, k=4)


# ---------------------------------------------------------------------------
# 2-D pods x nodes mesh (ISSUE 14)
# ---------------------------------------------------------------------------

#: tier-1 keeps a compile-lean slice — (1, 2) reuses the SAME memoized
#: shard_map programs as the d=2 leg of the 1-D sweep above (equal Mesh
#: ⇒ equal lru entry ⇒ zero extra compiles), so only the 2x2 leg pays a
#: fresh trace.  The full five-shape acceptance sweep lives on the slow
#: lane (test_full_2d_mesh_shape_sweep).
TIER1_2D = ((1, 2), (2, 2))
FULL_2D = ((1, 1), (1, 8), (2, 4), (4, 2), (8, 1))


def _mesh2d(p, n):
    import jax

    return pmesh.solver_mesh(jax.devices()[:p * n], pods_axis=p)


def _numpy_rounds_oracle(state, pods, cand_key, cand_node, rounds):
    """Pure-NumPy propose/accept rounds (quota-free, packed regime):
    the acceptance-decision oracle.  Mirrors _assign_rounds semantics —
    per-round best fitting candidate by the packed key, priority-prefix
    acceptance per contended node counting EVERY active proposer in
    order — with plain Python loops, so a tensor-kernel bug cannot hide
    in both implementations."""
    alloc = np.asarray(state.node_allocatable)
    valid_n = np.asarray(state.node_valid)
    requested = np.asarray(state.node_requested).copy()
    req = np.asarray(pods.requests)
    prio = np.asarray(pods.priority)
    pvalid = np.asarray(pods.valid)
    ck, cn = np.asarray(cand_key), np.asarray(cand_node)
    p = req.shape[0]
    order = np.lexsort((np.arange(p), -prio))
    assignments = np.full(p, -1, np.int32)
    active = pvalid & (ck >= 0).any(axis=1)
    for _ in range(rounds):
        if not active.any():
            break
        free = np.where(valid_n[:, None], alloc - requested, 0)
        cand_free = free[cn]
        fits = (((req[:, None, :] <= cand_free)
                 | (req[:, None, :] == 0)).all(-1)) & (ck >= 0)
        masked = np.where(fits, ck, -1)
        best = masked.argmax(axis=1)
        has = fits[np.arange(p), best]
        choice = cn[np.arange(p), best]
        act = active & has
        accept = np.zeros(p, bool)
        used: dict[int, np.ndarray] = {}
        for i in order:
            if not act[i]:
                continue
            c = int(choice[i])
            cum = used.get(c, 0) + req[i]
            if ((cum <= free[c]) | (req[i] == 0)).all():
                accept[i] = True
            used[c] = cum
        for i in np.where(accept)[0]:
            requested[choice[i]] += req[i]
            assignments[i] = choice[i]
        active = act & ~accept
    return assignments, requested


def test_program_cache_shared_across_equal_meshes():
    """The tier-1 budget guard: equal meshes (same devices, same axis
    split) built by different solver_mesh calls share ONE memoized
    shard_map program entry, so the 2-D sweep re-traces nothing the 1-D
    sweep already compiled."""
    import jax

    m1 = pmesh.solver_mesh(jax.devices()[:2])
    m2 = _mesh2d(1, 2)
    assert m1 == m2
    p1 = ps._select_program(m1, 64, K, (SB,))
    p2 = ps._select_program(m2, 64, K, (SB,))
    assert p1 is p2
    assert ps._select_program(_mesh2d(2, 1), 64, K, (SB,)) is not p1


def test_two_axis_selection_and_rounds_tier1():
    """The compile-lean 2-D slice: pod-sharded selection + quota-charged
    rounds bit-identical to single-device at 1x2 and 2x2."""
    state, pods = build_problem(n_nodes=64, n_pods=32)
    cfg = ScoringConfig.default()
    quota, pods = _quota_fixture(pods)
    ck, cn, cs = ba.select_candidates(state, pods, cfg, k=K,
                                      spread_bits=SB, method="exact",
                                      with_scores=True)
    a_ref, st_ref, q_ref = ba._assign_rounds(state, pods, quota, ck, cn,
                                             ROUNDS)
    valid = np.asarray(ck) >= 0
    for shape in TIER1_2D:
        mesh = _mesh2d(*shape)
        sck, scn, scs = ps.sharded_select_candidates(
            mesh, state, pods, cfg, k=K, spread_bits=SB,
            with_scores=True)
        np.testing.assert_array_equal(np.asarray(sck), np.asarray(ck),
                                      err_msg=f"keys {shape}")
        np.testing.assert_array_equal(
            np.asarray(scn)[valid], np.asarray(cn)[valid],
            err_msg=f"nodes {shape}")
        a, st, q = ps.sharded_assign_rounds(mesh, state, pods, quota,
                                            sck, scn, ROUNDS)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(a_ref),
                                      err_msg=f"assignments {shape}")
        np.testing.assert_array_equal(
            np.asarray(st.node_requested),
            np.asarray(st_ref.node_requested), err_msg=f"state {shape}")
        np.testing.assert_array_equal(
            np.asarray(q.headroom), np.asarray(q_ref.headroom),
            err_msg=f"quota {shape}")


def test_two_axis_rounds_match_numpy_oracle():
    """Acceptance decisions cross-checked against the pure-NumPy
    propose/accept oracle (not just the JAX single-device twin): device
    rounds at 2x2 == _assign_rounds == the Python loop."""
    state, pods = build_problem(n_nodes=64, n_pods=32, seed=23)
    cfg = ScoringConfig.default()
    ck, cn = ba.select_candidates(state, pods, cfg, k=K, spread_bits=SB,
                                  method="exact")
    a_ref, st_ref, _ = ba._assign_rounds(state, pods, None, ck, cn,
                                         ROUNDS)
    a_np, req_np = _numpy_rounds_oracle(state, pods, ck, cn, ROUNDS)
    np.testing.assert_array_equal(np.asarray(a_ref), a_np)
    np.testing.assert_array_equal(np.asarray(st_ref.node_requested),
                                  req_np)
    a_sh, st_sh, _ = ps.sharded_assign_rounds(
        _mesh2d(2, 2), state, pods, None, ck, cn, ROUNDS)
    np.testing.assert_array_equal(np.asarray(a_sh), a_np)
    np.testing.assert_array_equal(np.asarray(st_sh.node_requested),
                                  req_np)


def test_two_axis_gang_and_greedy_tier1():
    """The explicit shard_map gang twin (both per-pass engines) at 2x2
    == the GSPMD-placed gang_assign, quota-free (the quota-charged gang
    legs ride the slow-lane sweep)."""
    import jax

    from koordinator_tpu.ops.gang import GangInfo, gang_assign

    state, pods = build_problem(n_pods=32, seed=9)
    gang_id = np.full(pods.capacity, -1, np.int32)
    gang_id[:6] = 0
    pods = pods.replace(gang_id=np.asarray(gang_id))
    gangs = GangInfo.build(np.array([4], np.int32))
    cfg = ScoringConfig.default()
    mesh = _mesh2d(2, 2)
    f = jax.jit(gang_assign, static_argnames=("passes", "solver"))
    for solver in ("batch", "greedy"):
        a_ref, st_ref, _ = f(state, pods, cfg, gangs, None, passes=2,
                             solver=solver)
        a, st, _, _ = ps.sharded_gang_assign(mesh, state, pods, cfg, gangs,
                                          None, passes=2, solver=solver)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(a_ref),
                                      err_msg=solver)
        np.testing.assert_array_equal(
            np.asarray(st.node_requested),
            np.asarray(st_ref.node_requested), err_msg=solver)


def test_pod_capacity_must_divide_over_the_mesh():
    from koordinator_tpu.state.cluster_state import PodBatch

    state, _ = build_problem(n_nodes=64, n_pods=8)
    rng = np.random.default_rng(0)
    req = np.zeros((20, R), np.int32)
    req[:, CPU] = rng.integers(100, 1_000, 20)
    pods = PodBatch.build(req, node_capacity=64, capacity=20)
    cfg = ScoringConfig.default()
    with pytest.raises(ValueError, match="pods axis"):
        ps.sharded_select_candidates(_mesh2d(8, 1), state, pods, cfg,
                                     k=K)


def test_scheduler_two_axis_end_to_end():
    """Scheduler parity on a 2x2 pods x nodes mesh: same feed, same
    binds, same accounting as single-device, across rounds that cover
    the full-cold, incremental and sharded gang paths — the wiring
    (solve_sh routing, pod-axis batch pinning) on top of the kernel
    parity above."""
    from tests.test_incremental_solve import (
        _assert_no_overcommit,
        _feed_nodes,
        _mk_sched,
        _pod,
    )

    rng = np.random.default_rng(5)
    rng2 = np.random.default_rng(5)
    sharded = _mk_sched(True, mesh=_mesh2d(2, 2), shard_min_nodes=0)
    single = _mk_sched(True, mesh="off")
    assert sharded.kit.pod_shards == 2
    assert sharded.kit.shards == 2
    assert sharded.kit.mesh is not None
    for sched in (sharded, single):
        sched.incremental_dirty_threshold = 1.0
    _feed_nodes(sharded, rng, n=12)
    _feed_nodes(single, rng2, n=12)
    for rnd in range(3):
        for j in range(3):
            name = f"p{rnd}-{j}"
            sharded.enqueue(_pod(rng, name))
            single.enqueue(_pod(rng2, name))
        ra = sharded.schedule_round()
        rb = single.schedule_round()
        assert ra.assignments == rb.assignments, f"round {rnd}"
        assert set(ra.failures) == set(rb.failures), f"round {rnd}"
    _assert_no_overcommit(sharded)
    np.testing.assert_array_equal(
        np.asarray(sharded.snapshot.state.node_requested),
        np.asarray(single.snapshot.state.node_requested))
    rep = sharded.sharding_report()
    assert rep["mesh"] == {"pods": 2, "nodes": 2}
    assert rep["pod_shard_count"] == 2
    # per-(pod_shard, node_shard) byte keys (ISSUE 14 introspection)
    assert "p0n0" in rep["device_bytes_by_shard"]["cluster_state"]
    # the batch pins under the pod-axis NamedSharding
    assert sharded._batch_cache is not None
    batch = sharded._batch_cache[1]
    assert len(batch.requests.sharding.device_set) == 4


@pytest.mark.slow
def test_full_2d_mesh_shape_sweep():
    """The ISSUE 14 acceptance sweep: selection + quota-charged rounds,
    the 1%-dirty incremental refresh, gang placements (both engines,
    quota-charged) and the LP quality mode bit-identical to
    single-device across 1x1 / 1x8 / 2x4 / 4x2 / 8x1."""
    import jax
    import jax.numpy as jnp

    from koordinator_tpu.ops.gang import GangInfo, gang_assign
    from koordinator_tpu.quality.lp_pack import lp_pack_assign

    state, pods = build_problem(n_nodes=64, n_pods=32)
    cfg = ScoringConfig.default()
    quota, pods = _quota_fixture(pods)
    ck, cn, cs = ba.select_candidates(state, pods, cfg, k=K,
                                      spread_bits=SB, method="exact",
                                      with_scores=True)
    a_ref, st_ref, q_ref = ba._assign_rounds(state, pods, quota, ck, cn,
                                             ROUNDS)
    valid = np.asarray(ck) >= 0

    # gang reference (quota-charged, both engines)
    gang_id = np.full(pods.capacity, -1, np.int32)
    gang_id[:8] = 0
    gang_id[8:12] = 1
    gpods = pods.replace(gang_id=jnp.asarray(gang_id))
    gangs = GangInfo.build(np.array([6, 4], np.int32))
    gf = jax.jit(gang_assign, static_argnames=("passes", "solver"))
    gang_refs = {
        solver: gf(state, gpods, cfg, gangs, quota, passes=2,
                   solver=solver)
        for solver in ("batch", "greedy")}

    # dirty-refresh reference (~1% of a real cluster collapses here)
    cache = ba.CandidateCache(ck, cn, cs)
    dirty = [3, 40]
    dpad = _bucket(len(dirty), minimum=64)
    drows = np.zeros(dpad, np.int32)
    drows[: len(dirty)] = dirty
    dvalid = np.zeros(dpad, bool)
    dvalid[: len(dirty)] = True
    st_d = state.replace(
        node_usage=state.node_usage.at[jnp.asarray(dirty)].set(0))
    rk_ref, rc_ref = ba.refresh_candidates(
        st_d, pods, cfg, cache, jnp.asarray(drows), jnp.asarray(dvalid),
        k=K, spread_bits=SB)
    rvalid = np.asarray(rk_ref) >= 0

    # LP quality-mode reference (trimmed iteration bounds: the sweep's
    # evidence is mesh-shape invariance, not LP convergence depth)
    lp_ref = jax.jit(lp_pack_assign,
                     static_argnames=("ascent_iters", "rounding_iters"))(
        state, pods, cfg, ascent_iters=2, rounding_iters=2)

    for shape in FULL_2D:
        mesh = _mesh2d(*shape)
        sck, scn, _ = ps.sharded_select_candidates(
            mesh, state, pods, cfg, k=K, spread_bits=SB,
            with_scores=True)
        np.testing.assert_array_equal(np.asarray(sck), np.asarray(ck),
                                      err_msg=f"keys {shape}")
        np.testing.assert_array_equal(
            np.asarray(scn)[valid], np.asarray(cn)[valid],
            err_msg=f"nodes {shape}")
        a, st, q = ps.sharded_assign_rounds(mesh, state, pods, quota,
                                            sck, scn, ROUNDS)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(a_ref),
                                      err_msg=f"assignments {shape}")
        np.testing.assert_array_equal(
            np.asarray(q.headroom), np.asarray(q_ref.headroom),
            err_msg=f"quota {shape}")

        rk, rc = ps.sharded_refresh_candidates(
            mesh, st_d, pods, cfg, cache, jnp.asarray(drows),
            jnp.asarray(dvalid), k=K, spread_bits=SB)
        np.testing.assert_array_equal(np.asarray(rk), np.asarray(rk_ref),
                                      err_msg=f"refresh {shape}")
        np.testing.assert_array_equal(
            np.asarray(rc.cand_node)[rvalid],
            np.asarray(rc_ref.cand_node)[rvalid],
            err_msg=f"refresh nodes {shape}")

        for solver in ("batch", "greedy"):
            ga_ref, gst_ref, gq_ref = gang_refs[solver]
            ga, gst, gq, _ = ps.sharded_gang_assign(
                mesh, state, gpods, cfg, gangs, quota, passes=2,
                solver=solver)
            np.testing.assert_array_equal(
                np.asarray(ga), np.asarray(ga_ref),
                err_msg=f"gang {solver} {shape}")
            np.testing.assert_array_equal(
                np.asarray(gst.node_requested),
                np.asarray(gst_ref.node_requested),
                err_msg=f"gang state {solver} {shape}")
            np.testing.assert_array_equal(
                np.asarray(gq.headroom), np.asarray(gq_ref.headroom),
                err_msg=f"gang quota {solver} {shape}")

        la, lst, _, _ = ps.sharded_lp_pack_assign(
            mesh, state, pods, cfg, ascent_iters=2, rounding_iters=2)
        np.testing.assert_array_equal(np.asarray(la),
                                      np.asarray(lp_ref[0]),
                                      err_msg=f"lp {shape}")
        np.testing.assert_array_equal(
            np.asarray(lst.node_requested),
            np.asarray(lp_ref[1].node_requested),
            err_msg=f"lp state {shape}")


def test_scheduler_sharded_rounds_equal_single_device():
    """End-to-end Scheduler parity: the same feed solved by a
    sharded-by-default scheduler (8-way mesh engaged via
    shard_min_nodes=0) and a single-device one binds identical pods to
    identical nodes and charges identical quota, across steady-state
    rounds that exercise the incremental dirty path."""
    from tests.test_incremental_solve import (
        _assert_no_overcommit,
        _feed_nodes,
        _mk_sched,
        _pod,
    )

    rng = np.random.default_rng(3)
    sharded = _mk_sched(True, mesh="auto", shard_min_nodes=0)
    single = _mk_sched(True, mesh="off")
    assert sharded.kit.mesh is not None and sharded.kit.shards == 8
    assert single.kit.mesh is None
    for sched in (sharded, single):
        sched.incremental_dirty_threshold = 1.0
    rng2 = np.random.default_rng(3)
    _feed_nodes(sharded, rng, n=12)
    _feed_nodes(single, rng2, n=12)
    took_incremental = False
    for rnd in range(4):
        for j in range(3):
            name = f"p{rnd}-{j}"
            pa, pb = _pod(rng, name), _pod(rng2, name)
            sharded.enqueue(pa)
            single.enqueue(pb)
        ra = sharded.schedule_round()
        rb = single.schedule_round()
        assert ra.assignments == rb.assignments, f"round {rnd}"
        assert set(ra.failures) == set(rb.failures), f"round {rnd}"
        if sharded.last_solve_path == "incremental":
            took_incremental = True
    assert sharded.kit.sharding_active_for(sharded.snapshot.capacity)
    assert took_incremental, "incremental path never engaged while sharded"
    _assert_no_overcommit(sharded)
    np.testing.assert_array_equal(
        np.asarray(sharded.snapshot.state.node_requested),
        np.asarray(single.snapshot.state.node_requested))
