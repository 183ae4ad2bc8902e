"""Incremental delta-driven solve: the persistent candidate cache must be
EXACT, not just safe.

Two layers of property coverage:

- ops level: for random node/pod delta sequences, the dirty-column merge
  (+ dirty-pod rescore) must reproduce ``select_candidates``'s output
  bit-for-bit (valid slots: same nodes, same keys, same order) and the
  propose/accept rounds must produce identical assignments;
- scheduler level: a scheduler with the incremental path on must make the
  SAME acceptance decisions as one with it off, round for round, across
  arrivals, binds, node churn and usage refreshes — with the incremental
  path actually taken (asserted via ``last_solve_path``).

The cache-invalidation contract under test: a stale candidate may cost
recall, never correctness — acceptance re-checks fit and quota exactly
(no assignment may overcommit a node, asserted every round), and the
dirty tracking is what keeps recall exact.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tests.conftest import prop_seeds
from tests.problem_helpers import build_problem

from koordinator_tpu.api.resources import resource_vector
from koordinator_tpu.ops.batch_assign import (
    CandidateCache,
    _assign_rounds,
    align_candidate_cache,
    refresh_candidates,
    scatter_candidate_rows,
    select_candidates,
)
from koordinator_tpu.scheduler.scheduler import Scheduler
from koordinator_tpu.scheduler.solver_kit import SolverKit
from koordinator_tpu.scheduler.snapshot import (
    ClusterSnapshot,
    NodeSpec,
    PodSpec,
)
from koordinator_tpu.state.cluster_state import _bucket

K = 8
N_NODES = 64


# jitted once per process: the ops-level property loop re-invokes these
# dozens of times across steps and seeds — the jit cache amortizes the
# compile the way the scheduler's persistent wrappers do
_align_j = jax.jit(align_candidate_cache)
_refresh_j = jax.jit(refresh_candidates, static_argnames=("k",))
_scatter_j = jax.jit(scatter_candidate_rows)
_select_j = jax.jit(select_candidates,
                    static_argnames=("k", "method", "with_scores"))
_rounds_j = jax.jit(_assign_rounds, static_argnames=("rounds",))


def _incremental_step(state, pods, cache, dirty_rows, dirty_pod_rows):
    """One ops-level incremental refresh: merge dirty columns, rescore
    dirty pods, return the new cache — the same sequence
    Scheduler._solve_batch_incremental drives."""
    from koordinator_tpu.ops.assignment import ScoringConfig

    cfg = ScoringConfig.default()
    n = state.capacity
    p = pods.capacity
    dirty_np = np.zeros(n, bool)
    dirty_np[dirty_rows] = True
    dpad = _bucket(max(len(dirty_rows), 1), minimum=8)
    drows = np.zeros(dpad, np.int32)
    drows[: len(dirty_rows)] = dirty_rows
    dvalid = np.zeros(dpad, bool)
    dvalid[: len(dirty_rows)] = True
    aligned, touch = _align_j(
        cache, jnp.arange(p, dtype=jnp.int32), jnp.ones(p, bool),
        jnp.asarray(dirty_np))
    dirty_pods = np.asarray(touch).copy()
    dirty_pods[dirty_pod_rows] = True
    cand_key, cache = _refresh_j(
        state, pods, cfg, aligned, jnp.asarray(drows), jnp.asarray(dvalid),
        k=K)
    if dirty_pods.any():
        small, idx = pods.compact(dirty_pods)
        sk, sn, ss = _select_j(state, small, cfg, k=K,
                               method="exact", with_scores=True)
        rows_pad = np.full(small.capacity, p, np.int32)
        rows_pad[: len(idx)] = idx
        cache = _scatter_j(cache, jnp.asarray(rows_pad), sk, sn, ss)
    return cache


@pytest.mark.parametrize("seed", prop_seeds(2))
def test_refresh_matches_full_selection_random_deltas(seed):
    """Random delta sequences: merged candidates == full-pass candidates
    bit-for-bit, and the propose/accept assignments are identical."""
    from koordinator_tpu.ops.assignment import ScoringConfig

    cfg = ScoringConfig.default()
    rng = np.random.default_rng(seed)
    state, pods = build_problem(n_nodes=N_NODES, n_pods=192,
                                seed=seed, invalid_tail=4)
    ck, cn, cs = _select_j(state, pods, cfg, k=K, method="exact",
                           with_scores=True)
    cache = CandidateCache(ck, cn, cs)

    for step in range(6):
        # node delta: usage / requested / allocatable / validity flips
        rows = np.unique(rng.integers(0, N_NODES, rng.integers(1, 6)))
        usage = np.asarray(state.node_usage).copy()
        req = np.asarray(state.node_requested).copy()
        valid = np.asarray(state.node_valid).copy()
        usage[rows] = (usage[rows] * rng.uniform(0.3, 1.7)).astype(np.int32)
        alloc = np.asarray(state.node_allocatable)
        req[rows] = np.clip(
            req[rows] + rng.integers(-2_000, 4_000, req[rows].shape),
            0, alloc[rows]).astype(np.int32)
        flip = rows[rng.random(len(rows)) < 0.2]
        valid[flip] = ~valid[flip]
        state = state.replace(node_usage=jnp.asarray(usage),
                              node_requested=jnp.asarray(req),
                              node_valid=jnp.asarray(valid))
        # pod delta: a few pods change their requests ("new" pods)
        pd = np.unique(rng.integers(0, 192, rng.integers(0, 4)))
        if len(pd):
            preq = np.asarray(pods.requests).copy()
            preq[pd, 0] = rng.integers(100, 6_000, len(pd))
            pods = pods.replace(requests=jnp.asarray(preq))

        cache = _incremental_step(state, pods, cache, rows, pd)
        fk, fn = _select_j(state, pods, cfg, k=K, method="exact")

        fk_np, fn_np = np.asarray(fk), np.asarray(fn)
        ik_np, in_np = np.asarray(cache.cand_key), np.asarray(cache.cand_node)
        valid_slots = fk_np >= 0
        assert (valid_slots == (ik_np >= 0)).all(), f"step {step}: validity"
        assert (fk_np[valid_slots] == ik_np[valid_slots]).all(), \
            f"step {step}: keys diverged"
        assert (fn_np[valid_slots] == in_np[valid_slots]).all(), \
            f"step {step}: nodes diverged"

        fa, fst, _ = _rounds_j(state, pods, None, fk, fn, rounds=12)
        ia, ist, _ = _rounds_j(state, pods, None, cache.cand_key,
                               cache.cand_node, rounds=12)
        assert (np.asarray(fa) == np.asarray(ia)).all(), \
            f"step {step}: assignments diverged"
        # acceptance exactness: never overcommit, stale cache or not
        assert (np.asarray(ist.node_requested)
                <= np.asarray(ist.node_allocatable)
                ).all(axis=-1)[np.asarray(ist.node_valid)].all()


def _mk_sched(incremental: bool, quota_tree=None, mesh="off",
              shard_min_nodes=1024, **kw):
    # mesh="off" keeps this module's parity pairs on the single-device
    # path; tests/test_sharded_solve.py overrides with mesh="auto" +
    # shard_min_nodes=0 to run the same drivers over the 8-way mesh
    sched = Scheduler(ClusterSnapshot(capacity=32),
                      quota_tree=quota_tree,
                      batch_solver_threshold=1,   # force the batch engine
                      incremental_solve=incremental,
                      solver_kit=SolverKit(
                          mesh=mesh, shard_min_nodes=shard_min_nodes),
                      **kw)
    return sched


def _feed_nodes(sched, rng, n=12):
    for i in range(n):
        sched.snapshot.upsert_node(NodeSpec(
            name=f"n{i}",
            allocatable=resource_vector(
                cpu=int(rng.integers(8_000, 32_000)),
                memory=int(rng.integers(16_384, 65_536))),
            usage=resource_vector(cpu=int(rng.integers(0, 2_000)),
                                  memory=int(rng.integers(0, 4_096)))))


def _pod(rng, name):
    return PodSpec(
        name=name,
        requests=resource_vector(cpu=int(rng.integers(200, 4_000)),
                                 memory=int(rng.integers(256, 8_192))),
        priority=int(rng.integers(3_000, 9_999)))


def _assert_no_overcommit(sched):
    st = sched.snapshot.state
    ok = (np.asarray(st.node_requested)
          <= np.asarray(st.node_allocatable)).all(axis=-1)
    assert ok[np.asarray(st.node_valid)].all(), "node overcommitted"


@pytest.mark.parametrize("seed", prop_seeds(1))
def test_scheduler_incremental_equals_full(seed):
    """Round-for-round identical acceptance decisions between a scheduler
    with the incremental candidate cache and one without, across a random
    churn sequence (arrivals, binds draining the queue, node add/remove,
    usage refreshes)."""
    rng_a, rng_b = (np.random.default_rng(seed),
                    np.random.default_rng(seed))
    inc, full = _mk_sched(True), _mk_sched(False)
    # the small 12-node cluster makes bind deltas a large node FRACTION;
    # force the incremental path so churn exercises the merge machinery
    # (the fallback flip has its own test)
    inc.incremental_dirty_threshold = 1.0
    _feed_nodes(inc, rng_a)
    _feed_nodes(full, rng_b)

    pod_i = 0
    took_incremental = False
    for rnd in range(6):
        # arrivals (same on both sides)
        for _ in range(int(np.random.default_rng(seed * 101 + rnd
                                                 ).integers(1, 6))):
            name = f"p{pod_i}"
            pod_seed = seed * 1_000_003 + pod_i
            pod_i += 1
            inc.enqueue(_pod(np.random.default_rng(pod_seed), name))
            full.enqueue(_pod(np.random.default_rng(pod_seed), name))
        drv = np.random.default_rng(seed * 7919 + rnd)
        if rnd >= 2 and drv.random() < 0.5:
            # usage refresh on a couple of nodes
            for i in np.unique(drv.integers(0, 12, 2)):
                name = f"n{i}"
                if name not in inc.snapshot.node_specs:
                    continue
                spec = inc.snapshot.node_specs[name]
                import dataclasses as _dc

                new_usage = resource_vector(
                    cpu=int(drv.integers(0, 6_000)),
                    memory=int(drv.integers(0, 8_192)))
                inc.snapshot.upsert_node(_dc.replace(spec, usage=new_usage))
                full.snapshot.upsert_node(
                    _dc.replace(full.snapshot.node_specs[name],
                                usage=new_usage))
        if rnd == 5:
            # node churn: remove one, add a fresh one
            inc.snapshot.remove_node("n3")
            full.snapshot.remove_node("n3")
            extra = NodeSpec(name="n-extra",
                             allocatable=resource_vector(cpu=24_000,
                                                         memory=49_152))
            inc.snapshot.upsert_node(extra)
            full.snapshot.upsert_node(extra)

        ra = inc.schedule_round()
        rb = full.schedule_round()
        assert ra.assignments == rb.assignments, f"round {rnd}"
        assert set(ra.failures) == set(rb.failures), f"round {rnd}"
        _assert_no_overcommit(inc)
        if inc.last_solve_path == "incremental":
            took_incremental = True
    assert took_incremental, \
        "the incremental path never engaged over the steady-state rounds"


def test_scheduler_incremental_equals_full_with_quota():
    """Same equality under elastic-quota admission + charging."""
    from koordinator_tpu.api.resources import NUM_RESOURCE_DIMS
    from koordinator_tpu.quota.tree import QuotaTree

    def tree():
        total = np.zeros(NUM_RESOURCE_DIMS, np.int64)
        total[0], total[1] = 200_000, 400_000
        t = QuotaTree(total_resource=total)
        mn = np.zeros(NUM_RESOURCE_DIMS, np.int64)
        mn[0] = 20_000
        mx = np.full(NUM_RESOURCE_DIMS, 60_000, np.int64)
        t.add("qa", min=mn, max=mx)
        t.add("qb", min=mn, max=mx)
        t.refresh_runtime()
        return t

    rng = np.random.default_rng(11)
    inc, full = _mk_sched(True, tree()), _mk_sched(False, tree())
    _feed_nodes(inc, np.random.default_rng(11))
    _feed_nodes(full, np.random.default_rng(11))
    for rnd in range(4):
        for j in range(4):
            name = f"q{rnd}-{j}"
            quota = "qa" if j % 2 == 0 else "qb"
            pod = PodSpec(
                name=name,
                requests=resource_vector(
                    cpu=int(rng.integers(500, 8_000)),
                    memory=int(rng.integers(512, 8_192))),
                priority=5_000 + j, quota=quota)
            import copy

            inc.enqueue(pod)
            full.enqueue(copy.deepcopy(pod))
        ra = inc.schedule_round()
        rb = full.schedule_round()
        assert ra.assignments == rb.assignments, f"round {rnd}"
        assert set(ra.failures) == set(rb.failures), f"round {rnd}"
        _assert_no_overcommit(inc)


def test_dirty_fraction_fallback_flips_to_full_pass():
    """Crossing incremental_dirty_threshold must fall back to the full
    selection (observable via last_solve_path + the metrics counter) and
    still produce full-pass decisions."""
    from koordinator_tpu import metrics

    rng = np.random.default_rng(5)
    inc, full = _mk_sched(True), _mk_sched(False)
    inc.incremental_dirty_threshold = 0.0   # any delta ⇒ fallback
    _feed_nodes(inc, np.random.default_rng(5))
    _feed_nodes(full, np.random.default_rng(5))
    for i in range(3):
        p = _pod(np.random.default_rng(100 + i), f"p{i}")
        import copy

        inc.enqueue(p)
        full.enqueue(copy.deepcopy(p))
    before = metrics.incremental_solve_total.value(
        labels={"path": "full_fallback"})
    assert inc.schedule_round().assignments == \
        full.schedule_round().assignments
    assert inc.last_solve_path == "full_cold"
    # second round: cache exists, but threshold 0 forces the fallback
    # (the bind deltas from round 1 dirtied the assigned nodes)
    p = _pod(rng, "late")
    import copy

    inc.enqueue(p)
    full.enqueue(copy.deepcopy(p))
    ra, rb = inc.schedule_round(), full.schedule_round()
    assert ra.assignments == rb.assignments
    assert inc.last_solve_path == "full_fallback"
    assert metrics.incremental_solve_total.value(
        labels={"path": "full_fallback"}) == before + 1


def test_unchanged_queue_rounds_reuse_cache_without_rescore():
    """Repeated rounds over an unchanged, unschedulable queue must take
    the incremental path with ZERO dirty pods (the whole point: O(delta)
    instead of O(P·N) per steady-state round)."""
    from koordinator_tpu import metrics

    sched = _mk_sched(True)
    sched.snapshot.upsert_node(NodeSpec(
        name="small", allocatable=resource_vector(cpu=1_000, memory=1_024)))
    for i in range(4):
        sched.enqueue(PodSpec(
            name=f"big{i}",
            requests=resource_vector(cpu=50_000, memory=100_000),
            priority=5_000))
    r = sched.schedule_round()
    assert not r.assignments and sched.last_solve_path == "full_cold"
    r = sched.schedule_round()
    assert not r.assignments and sched.last_solve_path == "incremental"
    assert metrics.incremental_dirty_pods.value() == 0.0


@pytest.mark.slow
def test_incremental_speedup_at_shape():
    """The delta-scaling claim at 12,800p × 2,560n on CPU: a steady-state
    round with ≤1% dirty nodes/pods must run ≥5× faster than the full
    pass (the bench records the same numbers as extras)."""
    import time

    from koordinator_tpu.ops.assignment import ScoringConfig
    from koordinator_tpu.ops.batch_assign import (
        assign_round_pass,
        batch_assign,
    )

    cfg = ScoringConfig.default()
    state, pods = build_problem(n_nodes=2_560, n_pods=12_800, seed=42,
                                factored=False, classes=1)
    full = jax.jit(lambda s, p: batch_assign(s, p, cfg, k=16,
                                             method="exact")[0])
    np.asarray(full(state, pods))
    t_full = []
    for _ in range(3):
        t0 = time.perf_counter()
        np.asarray(full(state, pods))
        t_full.append(time.perf_counter() - t0)

    ck, cn, cs = select_candidates(state, pods, cfg, k=16, method="exact",
                                   with_scores=True)
    cache = CandidateCache(ck, cn, cs)
    dirty = np.arange(25)          # ~1% of 2,560 nodes
    dirty_pod_rows = np.arange(0)  # no pod churn
    refresh = jax.jit(lambda st, p, c, dr, dv: refresh_candidates(
        st, p, cfg, c, dr, dv, k=16))
    rounds = jax.jit(lambda st, p, ck_, cn_: assign_round_pass(
        st, p, None, ck_, cn_, cfg)[0])
    dpad = _bucket(len(dirty), minimum=8)
    drows = np.zeros(dpad, np.int32)
    drows[: len(dirty)] = dirty
    dvalid = np.zeros(dpad, bool)
    dvalid[: len(dirty)] = True

    def inc_round():
        k2, c2 = refresh(state, pods, cache, jnp.asarray(drows),
                         jnp.asarray(dvalid))
        return np.asarray(rounds(state, pods, k2, c2.cand_node))

    inc_round()  # compile
    t_inc = []
    for _ in range(3):
        t0 = time.perf_counter()
        inc_round()
        t_inc.append(time.perf_counter() - t0)
    speedup = float(np.median(t_full)) / max(float(np.median(t_inc)), 1e-9)
    assert speedup >= 5.0, (
        f"incremental round only {speedup:.1f}x faster "
        f"(full {np.median(t_full):.3f}s, inc {np.median(t_inc):.3f}s)")


def test_conservative_rebuild_after_donated_state_loss():
    """The donation disaster path: if a jitted solve fails at EXECUTION
    time its donated state buffers are gone.  rebuild_conservative must
    leave a live, never-overcommitting scheduler (fully-booked nodes,
    no crash) that recovers capacity through node churn/resync."""
    sched = _mk_sched(True)
    _feed_nodes(sched, np.random.default_rng(3))
    sched.enqueue(_pod(np.random.default_rng(1), "a"))
    sched.schedule_round()

    # simulate the post-donation failure: every state buffer deleted
    for leaf in jax.tree.leaves(sched.snapshot.state):
        leaf.delete()
    sched.snapshot.rebuild_conservative()
    sched._cand_cache = None

    sched.enqueue(_pod(np.random.default_rng(2), "b"))
    r = sched.schedule_round()
    assert "b" in r.failures and not r.assignments
    _assert_no_overcommit(sched)

    # a fresh node restores schedulability (its row starts clean)
    sched.snapshot.upsert_node(NodeSpec(
        name="fresh",
        allocatable=resource_vector(cpu=8_000, memory=16_384)))
    r2 = sched.schedule_round()
    assert r2.assignments.get("b") == "fresh"
    _assert_no_overcommit(sched)
