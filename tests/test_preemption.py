"""Preemption (PostFilter) tests.

Scenarios mirror the reference's preemption test surfaces:
- elasticquota/preempt_test.go — same-quota victim selection, canPreempt
  (non-preemptible / quota match), PDB grouping, quota-limit-driven eviction;
- coscheduling/core/preemption_test.go — job-level all-or-nothing preemption,
  lower-priority eligibility, nomination;
- upstream pickOneNodeForPreemption — lexicographic node choice.
"""

import jax.numpy as jnp
import numpy as np

from koordinator_tpu.api.resources import NUM_RESOURCE_DIMS, ResourceDim, resource_vector
from koordinator_tpu.ops.preemption import (
    ScheduledPods,
    pick_node,
    preempt_one,
    select_victims,
)
from koordinator_tpu.state.cluster_state import ClusterState

from tests.test_scheduler import mk_scheduler, node, plain_cfg, pod

R = NUM_RESOURCE_DIMS
CPU = ResourceDim.CPU


def cluster(*alloc_cpu, requested_cpu=None):
    n = len(alloc_cpu)
    alloc = np.zeros((n, R), np.int32)
    alloc[:, CPU] = alloc_cpu
    req = np.zeros((n, R), np.int32)
    if requested_cpu is not None:
        req[:, CPU] = requested_cpu
    return ClusterState.from_arrays(alloc, requested=req)


def sched_pods(nodes, cpus, pris, **kw):
    v = len(nodes)
    req = np.zeros((v, R), np.int32)
    req[:, CPU] = cpus
    return ScheduledPods.build(
        req, np.array(nodes, np.int32), priority=np.array(pris, np.int32), **kw
    )


def req(cpu):
    return jnp.asarray(resource_vector(cpu=cpu).astype(np.int32))


NO_PDB = jnp.zeros(1, jnp.int32)


def run_select(state, sp, cpu, pri, quota=-1, feasible=None, pdb=NO_PDB, **kw):
    if feasible is None:
        feasible = jnp.ones(state.capacity, bool)
    return select_victims(
        state, sp, req(cpu), jnp.int32(pri), jnp.int32(quota), feasible, pdb, **kw
    )


class TestSelectVictims:
    def test_minimal_victim_set_keeps_most_important(self):
        # node 0: 4 cpu, full with 4x1cpu pods of priorities 40,30,20,10.
        # A 2-cpu preemptor at pri 100 needs 2 victims; reprieve
        # most-important-first keeps 40 and 30, evicts 20 and 10.
        state = cluster(4_000, requested_cpu=[4_000])
        sp = sched_pods([0, 0, 0, 0], [1_000] * 4, [40, 30, 20, 10])
        out = run_select(state, sp, 2_000, 100)
        assert bool(out.eligible[0])
        assert np.asarray(out.victim)[:4].tolist() == [False, False, True, True]
        assert int(out.num_victims[0]) == 2

    def test_higher_priority_pods_never_victims(self):
        state = cluster(4_000, requested_cpu=[4_000])
        sp = sched_pods([0, 0], [2_000, 2_000], [200, 300])
        out = run_select(state, sp, 2_000, 100)
        assert not bool(out.eligible[0])
        assert not np.asarray(out.victim).any()

    def test_non_preemptible_excluded(self):
        # canPreempt: extension.IsPodNonPreemptible victims are skipped
        state = cluster(4_000, requested_cpu=[4_000])
        sp = sched_pods(
            [0, 0], [2_000, 2_000], [10, 10],
            non_preemptible=np.array([True, False]),
        )
        out = run_select(state, sp, 4_000, 100)
        # only one candidate (1x2cpu) but preemptor needs 4 -> not eligible
        assert not bool(out.eligible[0])
        out2 = run_select(state, sp, 2_000, 100)
        assert bool(out2.eligible[0])
        assert np.asarray(out2.victim)[:2].tolist() == [False, True]

    def test_same_quota_only(self):
        # canPreempt: podQuotaName == vicQuotaName (preempt.go:309)
        state = cluster(4_000, requested_cpu=[4_000])
        sp = sched_pods(
            [0, 0], [2_000, 2_000], [10, 10],
            quota_id=np.array([0, 1], np.int32),
        )
        out = run_select(
            state, sp, 2_000, 100, quota=0,
            quota_headroom=jnp.full(R, 2**30 - 1, jnp.int32),
            same_quota_only=True,
        )
        assert bool(out.eligible[0])
        assert np.asarray(out.victim)[:2].tolist() == [True, False]

    def test_quota_limit_forces_extra_victims(self):
        # reprievePod's usedLimit check: the node has room, but the quota is
        # at its runtime limit, so same-quota victims must free quota too.
        state = cluster(10_000, requested_cpu=[2_000])
        sp = sched_pods(
            [0, 0], [1_000, 1_000], [10, 20],
            quota_id=np.array([0, 0], np.int32),
        )
        headroom = jnp.zeros(R, jnp.int32)  # used == runtime
        out = run_select(
            state, sp, 2_000, 100, quota=0, quota_headroom=headroom,
            same_quota_only=True,
        )
        # both pods evicted despite 8 cpu free on the node
        assert bool(out.eligible[0])
        assert np.asarray(out.victim)[:2].tolist() == [True, True]

    def test_node_without_candidates_ineligible(self):
        # "No victims found" -> UnschedulableAndUnresolvable (preempt.go:152)
        state = cluster(4_000, 4_000, requested_cpu=[4_000, 0])
        sp = sched_pods([0], [4_000], [10])
        out = run_select(state, sp, 2_000, 100)
        assert bool(out.eligible[0])
        assert not bool(out.eligible[1])  # empty node: nothing to preempt
        # (the pod would have scheduled there in the main solve if it fit)

    def test_affinity_failure_not_fixed_by_preemption(self):
        state = cluster(4_000, requested_cpu=[4_000])
        sp = sched_pods([0], [4_000], [10])
        feasible = jnp.zeros(state.capacity, bool)
        out = run_select(state, sp, 2_000, 100, feasible=feasible)
        assert not bool(out.eligible[0])


class TestPdb:
    def test_pdb_budget_marks_violating(self):
        # one PDB covering both candidates with 1 disruption allowed: the
        # second (less important) match is violating; chosen node pays 1
        # violation only if both must go.
        state = cluster(4_000, requested_cpu=[4_000])
        sp = sched_pods(
            [0, 0, 0, 0], [1_000] * 4, [40, 30, 20, 10],
            pdb_id=np.array([0, 0, 0, 0], np.int32),
        )
        pdb = jnp.array([1], jnp.int32)
        out = run_select(state, sp, 2_000, 100, pdb=pdb)
        viol = np.asarray(out.violating)[:4]
        # importance order 40,30,20,10 -> first match ok, rest violating
        assert viol.tolist() == [False, True, True, True]
        assert bool(out.eligible[0])
        # violating candidates are reprieved first: 30 and 20 come back
        # before non-violating 40; victims minimize violations
        assert int(out.num_violating[0]) <= 2

    def test_pick_node_prefers_fewer_violations(self):
        # node 0 victims violate a PDB, node 1 victims do not -> node 1 wins
        # even though both fit.
        state = cluster(4_000, 4_000, requested_cpu=[4_000, 4_000])
        sp = sched_pods(
            [0, 1], [2_000, 2_000], [10, 10],
            pdb_id=np.array([0, -1], np.int32),
        )
        pdb = jnp.array([0], jnp.int32)  # no disruptions allowed
        out = run_select(state, sp, 2_000, 100, pdb=pdb)
        assert bool(out.eligible[0]) and bool(out.eligible[1])
        assert int(pick_node(out)) == 1

    def test_pick_node_prefers_lower_victim_priority(self):
        # equal violations: lowest highest-victim-priority wins
        state = cluster(4_000, 4_000, requested_cpu=[4_000, 4_000])
        sp = sched_pods([0, 1], [2_000, 2_000], [50, 10])
        out = run_select(state, sp, 2_000, 100)
        assert int(pick_node(out)) == 1


class TestPreemptOne:
    def test_commit_updates_state_and_pdb(self):
        state = cluster(4_000, requested_cpu=[4_000])
        sp = sched_pods(
            [0, 0], [2_000, 2_000], [10, 20],
            pdb_id=np.array([0, -1], np.int32),
        )
        pdb = jnp.array([5], jnp.int32)
        out = preempt_one(
            state, sp, req(2_000), jnp.int32(100), jnp.int32(-1),
            jnp.ones(state.capacity, bool), pdb,
        )
        assert int(out.node) == 0
        victims = np.asarray(out.victims)[:2]
        assert victims.tolist() == [True, False]  # keep the more important
        # victim's 2 cpu freed, preemptor's 2 cpu nominated
        assert int(out.state.node_requested[0, CPU]) == 4_000
        assert not bool(out.sched.valid[0])
        assert bool(out.sched.valid[1])
        assert int(out.pdb_allowed[0]) == 4

    def test_no_help_returns_minus_one(self):
        state = cluster(4_000, requested_cpu=[4_000])
        sp = sched_pods([0], [1_000], [500])
        out = preempt_one(
            state, sp, req(2_000), jnp.int32(100), jnp.int32(-1),
            jnp.ones(state.capacity, bool), NO_PDB,
        )
        assert int(out.node) == -1
        assert not np.asarray(out.victims).any()


class TestSchedulerPostFilter:
    # enable_preemption defaults to off unless a preempt_fn is wired (the
    # scheduler must not free accounting for pods nothing evicts); tests
    # opt in explicitly.
    def bind_all(self, sched, pods):
        for p in pods:
            sched.enqueue(p)
        res = sched.schedule_round()
        assert not res.failures, res.failures
        return res

    def test_preempt_then_bind_next_round(self):
        sched, _ = mk_scheduler([node("n1", cpu=4_000)], enable_preemption=True)
        self.bind_all(sched, [
            pod("low-a", cpu=2_000, priority=10),
            pod("low-b", cpu=2_000, priority=20),
        ])
        evictions = []
        sched.preempt_fn = lambda v, by: evictions.append((v, by))
        sched.enqueue(pod("high", cpu=2_000, priority=9_500))
        res = sched.schedule_round()
        assert "high" in res.failures
        node_name, victims = res.nominations["high"]
        assert node_name == "n1"
        assert victims == ["low-a"]  # least important evicted
        assert evictions == [("low-a", "high")]
        assert "fits on n1 after preempting [low-a]" in \
            res.failures["high"].message()
        assert "low-a" not in sched.bound
        # next round: the nominated pod lands on the freed node
        res2 = sched.schedule_round()
        assert res2.assignments == {"high": "n1"}
        assert not sched.nominations

    def test_preemption_policy_never(self):
        sched, _ = mk_scheduler([node("n1", cpu=4_000)], enable_preemption=True)
        self.bind_all(sched, [pod("low", cpu=4_000, priority=10)])
        sched.enqueue(pod("high", cpu=2_000, priority=9_500,
                          preemption_policy="Never"))
        res = sched.schedule_round()
        assert "high" in res.failures
        assert not res.nominations
        assert "low" in sched.bound

    def test_pdb_respected_in_eviction_accounting(self):
        from koordinator_tpu.scheduler.scheduler import PdbRecord

        sched, _ = mk_scheduler([node("n1", cpu=4_000)], enable_preemption=True)
        sched.register_pdb(PdbRecord("pdb1", {"app": "web"}, allowed=1))
        self.bind_all(sched, [
            pod("web-a", cpu=2_000, priority=10, labels={"app": "web"}),
            pod("web-b", cpu=2_000, priority=20, labels={"app": "web"}),
        ])
        sched.enqueue(pod("high", cpu=2_000, priority=9_500))
        res = sched.schedule_round()
        # budget allows 1 disruption: web-a (2nd match in importance order)
        # would be the violating eviction, so it is reprieved FIRST and the
        # in-budget web-b is evicted instead — PDB safety beats priority in
        # the reprieve order (filterPodsWithPDBViolation + reprieve loop).
        assert res.nominations["high"][1] == ["web-b"]
        assert sched.pdbs["pdb1"].allowed == 0

    def test_gang_preemption_all_or_nothing(self):
        from koordinator_tpu.scheduler.scheduler import GangRecord

        sched, _ = mk_scheduler(
            [node("n1", cpu=4_000), node("n2", cpu=4_000)],
            enable_preemption=True,
        )
        self.bind_all(sched, [
            pod("low-1", cpu=4_000, priority=10),
            pod("low-2", cpu=4_000, priority=10),
        ])
        sched.register_gang(GangRecord("job", min_member=2))
        sched.enqueue(pod("g1", cpu=4_000, priority=9_000, gang="job"))
        sched.enqueue(pod("g2", cpu=4_000, priority=9_000, gang="job"))
        res = sched.schedule_round()
        # both members preempt: one victim per node
        assert set(res.nominations) == {"g1", "g2"}
        all_victims = sorted(
            v for _, vs in res.nominations.values() for v in vs
        )
        assert all_victims == ["low-1", "low-2"]
        res2 = sched.schedule_round()
        assert set(res2.assignments) == {"g1", "g2"}

    def test_gang_preemption_fails_atomically(self):
        # only one node's victims can be preempted (the other node's pod is
        # non-preemptible): the gang needs both -> nothing is evicted
        sched, _ = mk_scheduler(
            [node("n1", cpu=4_000), node("n2", cpu=4_000)],
            enable_preemption=True,
        )
        self.bind_all(sched, [
            pod("low-1", cpu=4_000, priority=10),
            pod("hard", cpu=4_000, priority=10, non_preemptible=True),
        ])
        from koordinator_tpu.scheduler.scheduler import GangRecord

        sched.register_gang(GangRecord("job", min_member=2))
        sched.enqueue(pod("g1", cpu=4_000, priority=9_000, gang="job"))
        sched.enqueue(pod("g2", cpu=4_000, priority=9_000, gang="job"))
        res = sched.schedule_round()
        assert not res.nominations
        assert set(sched.bound) == {"low-1", "hard"}

    def test_unchecked_dim_deficit_does_not_block_preemption(self):
        # a quota declaring only cpu in max must not have preemption blocked
        # by a memory "deficit" (runtime < used on the undeclared dim)
        total = np.zeros(R, np.int64)
        total[CPU] = 4_000
        from koordinator_tpu.quota.tree import UNBOUNDED, QuotaTree

        tree = QuotaTree(total)
        mx = resource_vector(cpu=4_000).astype(np.int64)
        mx[1] = UNBOUNDED  # memory undeclared in max -> unchecked dim
        tree.add("q", min=resource_vector(cpu=4_000).astype(np.int64), max=mx)
        sched, _ = mk_scheduler(
            [node("n1", cpu=4_000)], quota_tree=tree, enable_preemption=True,
        )
        # the bound pod uses memory (undeclared dim) freely
        self.bind_all(sched, [pod("low", cpu=4_000, mem=2_048,
                                  priority=10, quota="q")])
        sched.enqueue(pod("high", cpu=4_000, mem=2_048,
                          priority=9_500, quota="q"))
        res = sched.schedule_round()
        assert res.nominations["high"][1] == ["low"]

    def test_gang_quota_headroom_not_double_spent(self):
        # two gang members of the same quota: the second member's dry run
        # must see the first member's nominated request charged
        total = np.zeros(R, np.int64)
        total[CPU] = 4_000
        from koordinator_tpu.quota.tree import QuotaTree

        tree = QuotaTree(total)
        tree.add("q", min=resource_vector(cpu=4_000).astype(np.int64),
                 max=resource_vector(cpu=4_000).astype(np.int64))
        sched, _ = mk_scheduler(
            [node("n1", cpu=8_000), node("n2", cpu=8_000)],
            quota_tree=tree, enable_preemption=True,
        )
        self.bind_all(sched, [
            pod("low-1", cpu=2_000, mem=0, priority=10, quota="q"),
            pod("low-2", cpu=2_000, mem=0, priority=10, quota="q"),
        ])
        from koordinator_tpu.scheduler.scheduler import GangRecord

        sched.register_gang(GangRecord("job", min_member=2))
        # each member needs 4k cpu quota; quota runtime is 4k total, victims
        # free 2k each -> only ONE member can ever fit the quota; the gang
        # must fail atomically with no evictions
        sched.enqueue(pod("g1", cpu=4_000, mem=0, priority=9_000,
                          gang="job", quota="q"))
        sched.enqueue(pod("g2", cpu=4_000, mem=0, priority=9_000,
                          gang="job", quota="q"))
        res = sched.schedule_round()
        assert not res.nominations
        assert set(sched.bound) == {"low-1", "low-2"}

    def test_nominated_gang_resolves_all_or_nothing(self):
        # both members nominated; one nominated node vanishes before the next
        # round -> NEITHER member binds (no partial gang below minMember)
        from koordinator_tpu.scheduler.scheduler import GangRecord

        sched, _ = mk_scheduler(
            [node("n1", cpu=4_000), node("n2", cpu=4_000)],
            enable_preemption=True,
        )
        self.bind_all(sched, [
            pod("low-1", cpu=4_000, priority=10),
            pod("low-2", cpu=4_000, priority=10),
        ])
        sched.register_gang(GangRecord("job", min_member=2))
        sched.enqueue(pod("g1", cpu=4_000, priority=9_000, gang="job"))
        sched.enqueue(pod("g2", cpu=4_000, priority=9_000, gang="job"))
        res = sched.schedule_round()
        assert set(res.nominations) == {"g1", "g2"}
        victim_node = res.nominations["g1"][0]
        other_node = res.nominations["g2"][0]
        assert {victim_node, other_node} == {"n1", "n2"}
        sched.snapshot.remove_node(other_node)  # g2's node vanishes
        res2 = sched.schedule_round()
        assert "g1" not in res2.assignments
        assert "g2" not in res2.assignments
        assert not sched.nominations  # released, will retry from scratch

    def test_multiple_pdbs_all_decremented(self):
        from koordinator_tpu.scheduler.scheduler import PdbRecord

        sched, _ = mk_scheduler([node("n1", cpu=4_000)], enable_preemption=True)
        sched.register_pdb(PdbRecord("pdb-a", {"app": "web"}, allowed=3))
        sched.register_pdb(PdbRecord("pdb-b", {"app": "web"}, allowed=2))
        self.bind_all(sched, [
            pod("low", cpu=4_000, priority=10, labels={"app": "web"}),
        ])
        sched.enqueue(pod("high", cpu=4_000, priority=9_500))
        res = sched.schedule_round()
        assert res.nominations["high"][1] == ["low"]
        assert sched.pdbs["pdb-a"].allowed == 2
        assert sched.pdbs["pdb-b"].allowed == 1

    def test_nominated_capacity_protected_from_other_pods(self):
        # the preemptor's resources are assumed on the nominated node: an
        # equal-priority pod enqueued later must NOT steal the freed capacity
        sched, _ = mk_scheduler([node("n1", cpu=4_000)], enable_preemption=True)
        self.bind_all(sched, [pod("low", cpu=4_000, priority=10)])
        sched.enqueue(pod("high", cpu=4_000, priority=9_500))
        res = sched.schedule_round()
        assert res.nominations["high"][1] == ["low"]
        # a rival created "earlier" (creation=0 vs default) at same priority
        sched.enqueue(pod("rival", cpu=4_000, priority=9_500, creation=-1.0))
        res2 = sched.schedule_round()
        assert res2.assignments.get("high") == "n1"
        assert "rival" in res2.failures

    def test_dequeue_clears_nomination_and_reservation(self):
        sched, _ = mk_scheduler([node("n1", cpu=4_000)], enable_preemption=True)
        self.bind_all(sched, [pod("low", cpu=4_000, priority=10)])
        sched.enqueue(pod("high", cpu=4_000, priority=9_500))
        sched.schedule_round()
        assert "high" in sched.nominations
        sched.dequeue("high")  # user deletes the preemptor
        assert not sched.nominations
        # the assumed reservation is released: another pod can use the node
        sched.enqueue(pod("other", cpu=4_000, priority=100))
        res = sched.schedule_round()
        assert res.assignments == {"other": "n1"}

    def test_quota_preemption_same_quota_victims(self):
        import numpy as np

        from koordinator_tpu.quota.tree import QuotaTree

        total = np.zeros(R, np.int64)
        total[CPU] = 8_000
        tree = QuotaTree(total)
        tree.add("team-a", min=resource_vector(cpu=4_000).astype(np.int64),
                 max=resource_vector(cpu=4_000).astype(np.int64))
        tree.add("team-b", min=resource_vector(cpu=4_000).astype(np.int64),
                 max=resource_vector(cpu=4_000).astype(np.int64))
        sched, _ = mk_scheduler(
            [node("n1", cpu=16_000)], quota_tree=tree, enable_preemption=True,
        )
        self.bind_all(sched, [
            pod("a-low", cpu=4_000, mem=0, priority=10, quota="team-a"),
            pod("b-low", cpu=4_000, mem=0, priority=10, quota="team-b"),
        ])
        # team-a is at its limit; a higher-pri team-a pod preempts ONLY the
        # team-a victim even though the node has free cpu
        sched.enqueue(pod("a-high", cpu=4_000, mem=0, priority=9_500, quota="team-a"))
        res = sched.schedule_round()
        assert res.nominations["a-high"][1] == ["a-low"]
        assert "b-low" in sched.bound
        res2 = sched.schedule_round()
        assert res2.assignments == {"a-high": "n1"}


class TestPreemptChain:
    """preempt_chain == sequential preempt_one + host commit (batched
    PostFilter), plus the scheduler-level round budget."""

    def _chain_problem(self, seed=0, n_nodes=6, n_bound=24, n_fail=8):
        rng = np.random.default_rng(seed)
        alloc = rng.integers(4_000, 12_000, n_nodes).astype(np.int32)
        bound_nodes = rng.integers(0, n_nodes, n_bound)
        bound_cpu = rng.integers(500, 3_000, n_bound).astype(np.int32)
        requested = np.zeros(n_nodes, np.int32)
        for nd, c in zip(bound_nodes, bound_cpu):
            requested[nd] += c
        requested = np.minimum(requested, alloc)
        state = cluster(*alloc.tolist(), requested_cpu=requested.tolist())
        sp = sched_pods(
            bound_nodes.tolist(), bound_cpu.tolist(),
            rng.integers(10, 90, n_bound).tolist(),
            quota_id=rng.integers(-1, 3, n_bound).astype(np.int32),
        )
        reqs = np.zeros((n_fail, R), np.int32)
        reqs[:, CPU] = rng.integers(2_000, 6_000, n_fail)
        pris = rng.integers(5_000, 9_000, n_fail).astype(np.int32)
        qids = rng.integers(-1, 3, n_fail).astype(np.int32)
        same_q = qids >= 0
        feas = rng.random((n_fail, state.capacity)) < 0.9
        base_hr = rng.integers(-2_000, 20_000,
                               (3, R)).astype(np.int32)
        pdb = jnp.zeros(1, jnp.int32)
        return state, sp, reqs, pris, qids, feas, same_q, base_hr, pdb

    def test_chain_matches_sequential(self):
        from koordinator_tpu.ops.preemption import (
            HEADROOM_OPEN,
            preempt_chain,
        )

        for seed in range(4):
            (state, sp, reqs, pris, qids, feas, same_q, base_hr,
             pdb) = self._chain_problem(seed=seed)
            n_fail = reqs.shape[0]
            out = preempt_chain(
                state, sp, jnp.asarray(reqs), jnp.asarray(pris),
                jnp.asarray(qids), jnp.asarray(feas),
                jnp.asarray(same_q), jnp.ones(n_fail, bool), pdb,
                jnp.asarray(base_hr),
            )
            # sequential reference: preempt_one per pod, with the same
            # commit-mirror quota accounting the chain carries
            cur_state, cur_sched, cur_pdb = state, sp, pdb
            assumed = np.zeros_like(base_hr)
            want_nodes = []
            want_victims = []
            for j in range(n_fail):
                qid = int(qids[j])
                if same_q[j]:
                    hr = np.clip(base_hr[qid] - assumed[qid],
                                 -HEADROOM_OPEN, HEADROOM_OPEN)
                else:
                    hr = np.full(R, HEADROOM_OPEN, np.int32)
                o = preempt_one(
                    cur_state, cur_sched, jnp.asarray(reqs[j]),
                    jnp.int32(pris[j]), jnp.int32(qid),
                    jnp.asarray(feas[j]), cur_pdb,
                    quota_headroom=jnp.asarray(hr.astype(np.int32)),
                    same_quota_only=bool(same_q[j]),
                )
                nd = int(o.node)
                want_nodes.append(nd)
                if nd < 0:
                    want_victims.append(np.zeros(sp.capacity, bool))
                    continue
                chosen = np.asarray(o.victims)
                want_victims.append(chosen)
                vq = np.asarray(cur_sched.quota_id)
                for v in np.flatnonzero(chosen):
                    if vq[v] >= 0:
                        assumed[vq[v]] -= np.asarray(sp.requests)[v]
                if qid >= 0:
                    assumed[qid] += reqs[j]
                cur_state, cur_sched, cur_pdb = o.state, o.sched, o.pdb_allowed
            assert np.asarray(out.node).tolist() == want_nodes, seed
            np.testing.assert_array_equal(
                np.asarray(out.victims), np.stack(want_victims))
            np.testing.assert_array_equal(
                np.asarray(out.state.node_requested),
                np.asarray(cur_state.node_requested))
            np.testing.assert_array_equal(
                np.asarray(out.sched.valid), np.asarray(cur_sched.valid))
            np.testing.assert_array_equal(
                np.asarray(out.pdb_allowed), np.asarray(cur_pdb))

    def test_inactive_rows_leave_carry_untouched(self):
        from koordinator_tpu.ops.preemption import preempt_chain

        (state, sp, reqs, pris, qids, feas, same_q, base_hr,
         pdb) = self._chain_problem(seed=5)
        n_fail = reqs.shape[0]
        active = np.zeros(n_fail, bool)
        active[0] = True
        out = preempt_chain(
            state, sp, jnp.asarray(reqs), jnp.asarray(pris),
            jnp.asarray(qids), jnp.asarray(feas), jnp.asarray(same_q),
            jnp.asarray(active), pdb, jnp.asarray(base_hr),
        )
        assert np.all(np.asarray(out.node)[1:] == -1)
        assert not np.asarray(out.victims)[1:].any()


class TestPreemptionBudget:
    def test_round_cap_bounds_preemptors(self):
        # 6 failed singles, cap 2: only the 2 highest-priority pods get
        # nominations this round; the rest stay failed and retry later
        sched, _ = mk_scheduler(
            [node(f"n{i}", cpu=4_000) for i in range(6)],
            enable_preemption=True,
        )
        sched.preempt_cap = 2
        for i in range(6):
            sched.enqueue(pod(f"low-{i}", cpu=4_000, priority=10))
        res = sched.schedule_round()
        assert not res.failures
        for i in range(6):
            sched.enqueue(pod(f"high-{i}", cpu=4_000,
                              priority=9_000 + 100 * i))
        res = sched.schedule_round()
        assert len(res.nominations) == 2
        # highest-priority failed pods won the budget
        assert set(res.nominations) == {"high-5", "high-4"}
        # next round the remaining pods get their turn
        res2 = sched.schedule_round()
        assert len(res2.nominations) == 2

    def test_chunked_singles_one_dispatch(self, monkeypatch):
        # consecutive single-pod preemptors ride ONE chain dispatch
        sched, _ = mk_scheduler(
            [node(f"n{i}", cpu=4_000) for i in range(4)],
            enable_preemption=True,
        )
        for i in range(4):
            sched.enqueue(pod(f"low-{i}", cpu=4_000, priority=10))
        assert not sched.schedule_round().failures
        calls = {"chain": 0, "one": 0}
        real_chain = sched._preempt_chain
        real_one = sched._preempt
        sched._preempt_chain = (
            lambda *a, **k: (calls.__setitem__("chain", calls["chain"] + 1)
                             or real_chain(*a, **k)))
        sched._preempt = (
            lambda *a, **k: (calls.__setitem__("one", calls["one"] + 1)
                             or real_one(*a, **k)))
        for i in range(4):
            sched.enqueue(pod(f"high-{i}", cpu=4_000, priority=9_000))
        res = sched.schedule_round()
        assert len(res.nominations) == 4
        assert calls == {"chain": 1, "one": 0}
