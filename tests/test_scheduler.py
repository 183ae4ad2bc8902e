import jax.numpy as jnp
import numpy as np

from koordinator_tpu.api.resources import NUM_RESOURCE_DIMS, ResourceDim, resource_vector
from koordinator_tpu.ops.assignment import ScoringConfig
from koordinator_tpu.quota.tree import UNBOUNDED, QuotaTree
from koordinator_tpu.scheduler import ClusterSnapshot, NodeSpec, PodSpec, Scheduler
from koordinator_tpu.scheduler.scheduler import GangRecord

R = NUM_RESOURCE_DIMS
CPU, MEM = ResourceDim.CPU, ResourceDim.MEMORY


def plain_cfg():
    return ScoringConfig.default().replace(
        usage_thresholds=jnp.zeros(R, jnp.int32),
        estimator_defaults=jnp.zeros(R, jnp.int32),
    )


def node(name, cpu=16_000, mem=65_536, usage_cpu=0, labels=None):
    usage = np.zeros(R, np.int32)
    usage[CPU] = usage_cpu
    return NodeSpec(
        name=name,
        allocatable=resource_vector(cpu=cpu, memory=mem),
        usage=usage,
        labels=labels or {},
    )


def pod(name, cpu=1_000, mem=1_024, **kw):
    return PodSpec(name=name, requests=resource_vector(cpu=cpu, memory=mem), **kw)


def mk_scheduler(nodes, **kw):
    snap = ClusterSnapshot(capacity=16)
    for n in nodes:
        snap.upsert_node(n)
    binds = []
    sched = Scheduler(
        snap, config=kw.pop("config", plain_cfg()),
        bind_fn=lambda p, n: binds.append((p, n)), **kw,
    )
    return sched, binds


def test_basic_round_binds_pods():
    sched, binds = mk_scheduler([node("n1"), node("n2")])
    sched.enqueue(pod("p1", cpu=4_000))
    sched.enqueue(pod("p2", cpu=4_000))
    res = sched.schedule_round()
    assert set(res.assignments) == {"p1", "p2"}
    assert not res.failures
    assert len(binds) == 2
    assert not sched.pending
    # accounting persists: a third round sees the reserved capacity
    sched.enqueue(pod("p3", cpu=14_000))
    res2 = sched.schedule_round()
    assert "p3" in res2.failures  # 12k free per node at most
    msg = res2.failures["p3"].message()
    assert "insufficient resources" in msg


def test_node_selector_routes_pod():
    sched, _ = mk_scheduler([
        node("gpu-node", labels={"pool": "gpu"}),
        node("cpu-node", labels={"pool": "cpu"}),
    ])
    sched.enqueue(pod("p1", node_selector={"pool": "gpu"}))
    res = sched.schedule_round()
    assert res.assignments["p1"] == "gpu-node"


def test_node_remove_and_delta_flush():
    sched, _ = mk_scheduler([node("n1"), node("n2")])
    sched.snapshot.remove_node("n2")
    sched.enqueue(pod("p1", node_selector={}))
    res = sched.schedule_round()
    assert res.assignments["p1"] == "n1"
    # re-add with new capacity; delta flush picks it up
    sched.snapshot.upsert_node(node("n2", cpu=32_000))
    sched.enqueue(pod("p2", cpu=20_000))
    res2 = sched.schedule_round()
    assert res2.assignments["p2"] == "n2"


def test_snapshot_grows_past_capacity():
    snap = ClusterSnapshot(capacity=4)
    for i in range(10):
        snap.upsert_node(node(f"n{i}"))
    snap.flush()
    assert snap.capacity >= 10
    assert int(np.asarray(snap.state.node_valid).sum()) == 10


def test_gang_wait_time_rejection():
    t = [0.0]
    sched, _ = mk_scheduler([node("n1", cpu=4_000)], clock=lambda: t[0])
    sched.register_gang(GangRecord(name="g", min_member=2, wait_time_sec=100))
    sched.enqueue(pod("g1", cpu=3_000, gang="g"))
    sched.enqueue(pod("g2", cpu=3_000, gang="g"))
    res = sched.schedule_round()
    assert not res.assignments  # gang can't fit together
    t[0] = 50.0
    sched.schedule_round()
    assert not sched.gangs["g"].rejected
    t[0] = 200.0
    sched.schedule_round()  # past wait time -> rejected
    assert sched.gangs["g"].rejected
    # rejected gang pods no longer enter rounds
    res4 = sched.schedule_round()
    assert res4.round_pods == 0


def test_gang_schedules_when_feasible():
    sched, binds = mk_scheduler([node("n1"), node("n2")])
    sched.register_gang(GangRecord(name="g", min_member=3))
    for i in range(3):
        sched.enqueue(pod(f"g{i}", cpu=6_000, gang="g"))
    res = sched.schedule_round()
    assert len(res.assignments) == 3


def test_quota_accounting_across_rounds():
    mx = np.full(R, UNBOUNDED, np.int64)
    mx[CPU], mx[MEM] = 5_000, 131_072
    tree = QuotaTree(resource_vector(cpu=32_000, memory=131_072).astype(np.int64))
    tree.add("team", min=np.zeros(R, np.int64), max=mx)
    sched, _ = mk_scheduler([node("n1"), node("n2")], quota_tree=tree)

    sched.enqueue(pod("p1", cpu=3_000, quota="team"))
    res1 = sched.schedule_round()
    assert "p1" in res1.assignments
    # round 2: only 2000m quota left
    sched.enqueue(pod("p2", cpu=3_000, quota="team"))
    res2 = sched.schedule_round()
    assert "p2" in res2.failures
    assert res2.failures["p2"].quota_rejected or res2.failures["p2"].feasible_nodes == 0
    sched.enqueue(pod("p3", cpu=1_500, quota="team"))
    res3 = sched.schedule_round()
    assert "p3" in res3.assignments


def test_row_reuse_does_not_inherit_requested():
    # bind onto n2, remove it, add n3 (reuses the row): n3 must start clean
    sched, _ = mk_scheduler([node("n1", cpu=1_000), node("n2")])
    sched.enqueue(pod("p1", cpu=15_000))
    res = sched.schedule_round()
    assert res.assignments["p1"] == "n2"
    sched.snapshot.remove_node("n2")
    sched.snapshot.upsert_node(node("n3"))
    sched.enqueue(pod("p2", cpu=15_000))  # only fits a clean 16k node
    res2 = sched.schedule_round()
    assert res2.assignments.get("p2") == "n3"


def test_unknown_quota_name_does_not_crash_bind():
    tree = QuotaTree(resource_vector(cpu=32_000, memory=131_072).astype(np.int64))
    mx = np.full(R, UNBOUNDED, np.int64)
    mx[CPU] = 32_000
    tree.add("real", min=np.zeros(R, np.int64), max=mx)
    sched, binds = mk_scheduler([node("n1")], quota_tree=tree)
    sched.enqueue(pod("p1", quota="typo-not-a-quota"))
    res = sched.schedule_round()
    assert "p1" in res.assignments  # quota_id -1: schedules unconstrained
    assert binds


def test_monitor_collects_phase_timings():
    """The round's per-phase walls (what the flight record snapshots)."""
    sched, _ = mk_scheduler([node("n1")])
    sched.enqueue(pod("p1"))
    sched.schedule_round()
    timings = sched.monitor.round_timings
    for phase in ("PreEnqueue", "BatchBuild", "Solve", "Bind"):
        assert timings[phase] > 0
    assert sched.flight_recorder.last().phase_s == timings


def test_second_pass_places_a_leftover_pod():
    """A gangless batch round whose SECOND pass places a pod: pass 1's
    assignments are written into on the host, so they must be a copy
    (``np.asarray`` of a device array is a read-only view and raised
    ``assignment destination is read-only`` here, on the CPU too)."""
    nodes = [node("n1", usage_cpu=0), node("n2", usage_cpu=4_000),
             node("n3", usage_cpu=8_000)]
    sched, _ = mk_scheduler(nodes, batch_solver_threshold=1)
    assert sched.gang_passes == 2
    # one propose/accept round a pass: every pod proposes the emptiest
    # node, the first in priority order takes it, the rest are left over
    sched.kit.rounds = 1
    second_pass = []
    pass2 = sched.kit.pass2

    def spy(*args, **kwargs):
        out = pass2(*args, **kwargs)
        second_pass.append(np.asarray(out[0]))
        return out

    sched.kit.pass2 = spy
    for i, prio in enumerate((9_000, 8_000, 7_000)):
        sched.enqueue(pod(f"p{i}", cpu=10_000, priority=prio))
    res = sched.schedule_round()
    assert sched.last_solver == "batch"
    assert second_pass and (second_pass[0] >= 0).any()
    assert sorted(res.assignments.values()) == ["n1", "n2", "n3"]
    assert res.assignments["p0"] == "n1"
    assert not res.failures


def test_diagnosis_message_shape():
    sched, _ = mk_scheduler([node("n1", cpu=1_000)])
    sched.enqueue(pod("big", cpu=50_000))
    res = sched.schedule_round()
    d = res.failures["big"]
    assert d.total_nodes == 1
    assert d.insufficient_resources == 1
    assert "1 insufficient resources" in d.message()


def test_topology_gang_gathers_in_one_block():
    from koordinator_tpu.ops.network_topology import (
        TopologyRequirements,
        TopologyTree,
    )

    # 2 blocks x 2 nodes; rows in the snapshot match tree add order
    tree = TopologyTree(["block", "node"])
    nodes = []
    for i in range(4):
        name = f"n{i}"
        tree.add_node([f"b{i // 2}", name])
        nodes.append(node(name, cpu=8_000))
    sched, _ = mk_scheduler(nodes, topology_tree=tree.build(capacity=16))
    # 2 pods of 8000 must gather at the block layer (one per node of a block)
    sched.register_gang(GangRecord(
        name="g", min_member=2,
        topology=TopologyRequirements(desired_slots=2, must_gather_layer=1),
    ))
    for i in range(2):
        sched.enqueue(pod(f"g{i}", cpu=8_000, gang="g"))
    res = sched.schedule_round()
    assert len(res.assignments) == 2
    placed = sorted(res.assignments.values())
    assert placed in (["n0", "n1"], ["n2", "n3"])  # same block


def test_topology_gang_infeasible_backs_off():
    from koordinator_tpu.ops.network_topology import (
        TopologyRequirements,
        TopologyTree,
    )

    tree = TopologyTree(["block", "node"])
    nodes = []
    for i in range(4):
        tree.add_node([f"b{i // 2}", f"n{i}"])
        nodes.append(node(f"n{i}", cpu=8_000))
    sched, _ = mk_scheduler(nodes, topology_tree=tree.build(capacity=16))
    # 3 full-node pods cannot gather within any 2-node block
    sched.register_gang(GangRecord(
        name="g", min_member=3,
        topology=TopologyRequirements(desired_slots=3, must_gather_layer=1),
    ))
    for i in range(3):
        sched.enqueue(pod(f"g{i}", cpu=8_000, gang="g"))
    res = sched.schedule_round()
    assert not res.assignments


def test_topology_gang_surplus_members_not_invalidated():
    from koordinator_tpu.ops.network_topology import (
        TopologyRequirements,
        TopologyTree,
    )

    tree = TopologyTree(["block", "node"])
    nodes = []
    for i in range(4):
        tree.add_node([f"b{i // 2}", f"n{i}"])
        nodes.append(node(f"n{i}", cpu=8_000))
    sched, _ = mk_scheduler(nodes, topology_tree=tree.build(capacity=16))
    # 3 members pending, plan covers desired_slots=2 -> the third member
    # schedules freely instead of killing the gang
    sched.register_gang(GangRecord(
        name="g", min_member=2,
        topology=TopologyRequirements(desired_slots=2, must_gather_layer=1),
    ))
    for i in range(3):
        sched.enqueue(pod(f"g{i}", cpu=4_000, gang="g"))
    res = sched.schedule_round()
    assert len(res.assignments) == 3


# ---- hot-path caching (no per-round host rework) ----------------------------

def test_quota_runtime_cached_between_unchanged_rounds():
    t = QuotaTree(total_resource=resource_vector(cpu=10_000).astype(np.int64))
    t.add("a", min=resource_vector(cpu=2_000).astype(np.int64),
          max=resource_vector(cpu=8_000).astype(np.int64))
    t.set_request("a", resource_vector(cpu=4_000).astype(np.int64))
    assert t.refresh_runtime() is True
    n = t.runtime_refreshes
    assert t.refresh_runtime() is False          # nothing changed: skipped
    assert t.runtime_refreshes == n
    t.set_request("a", resource_vector(cpu=5_000).astype(np.int64))
    assert t.refresh_runtime() is True           # request moved: recompute
    assert t.refresh_runtime(force=True) is True # force always recomputes


def test_batch_reused_across_unchanged_rounds():
    sched, _ = mk_scheduler([node("n1")])
    sched.enqueue(pod("big", cpu=99_000))        # never schedulable
    sched.schedule_round()
    assert sched.batch_rebuilds == 1
    sched.schedule_round()                       # same pending queue
    assert sched.batch_rebuilds == 1             # cache hit
    sched.enqueue(pod("tiny", cpu=100))
    res = sched.schedule_round()                 # queue changed: rebuild
    assert sched.batch_rebuilds == 2
    assert res.assignments == {"tiny": "n1"}
    sched.schedule_round()                       # tiny bound: queue changed
    assert sched.batch_rebuilds == 3


def test_batch_cache_invalidated_by_node_change():
    sched, _ = mk_scheduler([node("n1", cpu=1_000)])
    sched.enqueue(pod("p", cpu=4_000))
    res = sched.schedule_round()
    assert "p" in res.failures
    # capacity arrives: same pending queue, but snapshot grew a class/row
    for i in range(20):                          # force capacity growth
        sched.snapshot.upsert_node(node(f"x{i}", cpu=16_000))
    res = sched.schedule_round()
    assert "p" in res.assignments


def test_batch_cache_invalidated_by_new_class_within_bucket():
    # a new label equivalence class must invalidate even when neither the
    # row capacity nor the class padding bucket grows
    sched, _ = mk_scheduler([node("n1")])
    sched.enqueue(PodSpec(name="gpu-pod",
                          requests=resource_vector(cpu=1_000, memory=1_024),
                          node_selector={"gpu": "true"}))
    res = sched.schedule_round()
    assert "gpu-pod" in res.failures
    sched.snapshot.upsert_node(node("g1", labels={"gpu": "true"}))
    res = sched.schedule_round()
    assert res.assignments == {"gpu-pod": "g1"}


def test_scheduler_switches_to_batch_solver_at_scale():
    # below the threshold: exact greedy; at/above: the data-parallel engine.
    # last_solver records which engine actually ran.
    sched, binds = mk_scheduler(
        [node(f"n{i}", cpu=64_000) for i in range(8)],
        batch_solver_threshold=4)
    for i in range(3):
        sched.enqueue(pod(f"small-{i}", cpu=1_000))
    res = sched.schedule_round()           # 3 pods < 4: greedy
    assert sched.last_solver == "greedy"
    assert len(res.assignments) == 3
    for i in range(6):
        sched.enqueue(pod(f"big-{i}", cpu=1_000))
    res = sched.schedule_round()           # 6 pods >= 4: batch engine
    assert sched.last_solver == "batch"
    assert len(res.assignments) == 6
    assert len(binds) == 9


def test_batch_solver_failures_get_exact_rescue():
    # a genuinely unschedulable pod must fail with REAL diagnosis even
    # through the batch engine (the rescue pass re-solves leftovers
    # exactly, so approximation failures never masquerade as capacity
    # failures); schedulable leftovers get placed by the rescue
    sched, _ = mk_scheduler(
        [node("n1", cpu=4_000)], batch_solver_threshold=2)
    sched.enqueue(pod("fits", cpu=1_000))
    sched.enqueue(pod("too-big", cpu=50_000))
    res = sched.schedule_round()
    assert sched.last_solver == "batch"
    assert res.assignments == {"fits": "n1"}
    assert "too-big" in res.failures
    assert res.failures["too-big"].insufficient_resources == 1


def test_batch_engine_with_gangs_and_quota_contention():
    # the full stack through the batch engine: gang all-or-nothing + quota
    # caps + rescue, at a queue size over the threshold
    mx = np.full(R, UNBOUNDED, np.int64)
    mx[CPU] = 8_000
    tree = QuotaTree(resource_vector(cpu=64_000, memory=262_144).astype(np.int64))
    tree.add("team", min=np.zeros(R, np.int64), max=mx)
    sched, _ = mk_scheduler(
        [node(f"n{i}", cpu=16_000) for i in range(4)],
        quota_tree=tree, batch_solver_threshold=4)
    sched.register_gang(GangRecord(name="g", min_member=3))
    for i in range(3):
        sched.enqueue(pod(f"g{i}", cpu=4_000, gang="g"))       # gang fits
    for i in range(4):
        sched.enqueue(pod(f"q{i}", cpu=3_000, quota="team"))   # cap 8000: 2 fit
    res = sched.schedule_round()
    assert sched.last_solver == "batch"
    assert all(f"g{i}" in res.assignments for i in range(3))
    placed_q = [f"q{i}" for i in range(4) if f"q{i}" in res.assignments]
    assert len(placed_q) == 2              # quota admits floor(8000/3000)
    failed_q = [f"q{i}" for i in range(4) if f"q{i}" in res.failures]
    assert len(failed_q) == 2
    for name in failed_q:
        assert res.failures[name].quota_rejected   # real reason, not approx


def test_rescue_places_surplus_members_of_satisfied_gang():
    # 5 members, min_member=3: even if the batch engine strands surplus
    # members, the rescue must bind them individually (min is already met)
    sched, _ = mk_scheduler(
        [node(f"n{i}", cpu=16_000) for i in range(8)],
        batch_solver_threshold=2)
    sched.register_gang(GangRecord(name="g", min_member=3))
    for i in range(5):
        sched.enqueue(pod(f"g{i}", cpu=2_000, gang="g"))
    res = sched.schedule_round()
    assert sched.last_solver == "batch"
    assert len(res.assignments) == 5 and not res.failures


class TestExactScanStepCounter:
    """The exact scan's trip count on the round's flight record and in
    ``solver_greedy_scan_rows_total``: rows handed to it, the steps it
    took, the rest pruned at its entry."""

    @staticmethod
    def counted(outcome):
        from koordinator_tpu import metrics

        return metrics.greedy_scan_rows.value({"outcome": outcome})

    @staticmethod
    def standing_round(sched, standing=39):
        """One batch round that leaves ``standing`` pods no node holds, and
        a gang of four of which three fit and one never can: the batch
        engine rolls the gang back whole, so all four come back as
        leftovers — three of them live when the rescue scan starts."""
        for i in range(standing):
            sched.enqueue(pod(f"standing-{i}", cpu=900_000))
        sched.register_gang(GangRecord(name="g", min_member=4))
        for i in range(3):
            sched.enqueue(pod(f"g{i}", cpu=2_000, gang="g"))
        sched.enqueue(pod("g-wide", cpu=900_000, gang="g"))
        for i in range(5):
            sched.enqueue(pod(f"plain-{i}", cpu=1_000))
        return sched.schedule_round()

    def test_rescue_rows_and_steps_on_the_record_and_the_counter(self):
        sched, _ = mk_scheduler([node(f"n{i}") for i in range(4)],
                                batch_solver_threshold=2)
        res = self.standing_round(sched)
        assert sched.last_solver == "batch"
        assert set(res.assignments) == {f"plain-{i}" for i in range(5)}
        assert len(res.failures) == 43
        rec = sched.flight_recorder.last()
        # 40 rows that fit no node, 3 that do: only those are stepped
        assert (rec.rescue_rows, rec.rescue_steps) == (43, 3)
        assert (rec.prepass_rows, rec.prepass_steps) == (0, 0)
        assert self.counted("stepped") == 3
        assert self.counted("pruned") == 40
        # every standing pod is still diagnosed, with its real reason
        assert res.failures["standing-0"].insufficient_resources == 4
        assert rec.to_doc()["rescue_steps"] == 3

    def test_round_without_a_rescue_pass_records_nothing(self):
        sched, _ = mk_scheduler([node(f"n{i}") for i in range(4)],
                                batch_solver_threshold=2)
        for i in range(5):
            sched.enqueue(pod(f"plain-{i}", cpu=1_000))
        res = sched.schedule_round()
        assert sched.last_solver == "batch" and len(res.assignments) == 5
        rec = sched.flight_recorder.last()
        assert (rec.rescue_rows, rec.rescue_steps) == (0, 0)
        assert self.counted("stepped") == self.counted("pruned") == 0

    def test_greedy_round_is_counted_and_is_no_rescue(self):
        # below the threshold the exact scan IS the solve
        sched, _ = mk_scheduler([node("n1", cpu=4_000)],
                                batch_solver_threshold=64)
        sched.enqueue(pod("fits", cpu=1_000))
        sched.enqueue(pod("too-big", cpu=50_000))
        res = sched.schedule_round()
        assert sched.last_solver == "greedy"
        assert res.assignments == {"fits": "n1"}
        rec = sched.flight_recorder.last()
        assert (rec.rescue_rows, rec.rescue_steps) == (0, 0)
        assert self.counted("stepped") == 1 and self.counted("pruned") == 1

    def test_reading_the_steps_adds_no_device_wait(self):
        """The count rides the rescue solve's own program and the one
        block on its assignments: a round with a rescue pass blocks twice
        (main solve, rescue), as it did before there was a count."""
        from koordinator_tpu import timeline

        enabled = timeline.RECORDER.enabled
        timeline.RECORDER.set_enabled(True)
        try:
            sched, _ = mk_scheduler([node(f"n{i}") for i in range(4)],
                                    batch_solver_threshold=2)
            self.standing_round(sched)
            doc = next(d for d in timeline.RECORDER.cycles(4)
                       if d["mode"] == "round")
            assert doc["by_name"]["block_until_ready"]["n"] == 2
            assert sched.flight_recorder.last().rescue_steps == 3
        finally:
            timeline.RECORDER.set_enabled(enabled)

    def test_reservation_prepass_stamps_its_own_pair(self):
        from koordinator_tpu.scheduler.reservations import (
            OwnerMatcher,
            ReservationSpec,
        )

        sched, _ = mk_scheduler([node("n1"), node("n2")])
        sched.add_reservation(ReservationSpec(
            name="r-fits", requests=resource_vector(cpu=2_000, memory=1_024),
            owners=[OwnerMatcher(labels={"app": "a"})]))
        sched.add_reservation(ReservationSpec(
            name="r-wide", requests=resource_vector(cpu=900_000, memory=1_024),
            owners=[OwnerMatcher(labels={"app": "b"})]))
        sched.schedule_round()
        rec = sched.flight_recorder.last()
        # two reserve-pods through the exact pre-pass: one can be placed
        assert (rec.prepass_rows, rec.prepass_steps) == (2, 1)
        assert (rec.rescue_rows, rec.rescue_steps) == (0, 0)
        assert self.counted("stepped") == 1 and self.counted("pruned") == 1


class TestReservationRounds:
    """Reservation lifecycle through the round loop (plugins/reservation:
    reserve-pod placement, owner allocation, expiration)."""

    def _spec(self, name="rsv-a", cpu=8_000, node=None, ttl=None,
              labels=None, allocate_once=False):
        from koordinator_tpu.scheduler.reservations import (
            OwnerMatcher, ReservationSpec,
        )

        return ReservationSpec(
            name=name, requests=resource_vector(cpu=cpu, memory=8_192),
            owners=[OwnerMatcher(labels=labels or {"app": "web"})],
            node=node, ttl_sec=ttl, allocate_once=allocate_once,
        )

    def test_reserve_pod_places_and_hides_capacity(self):
        sched, _ = mk_scheduler([node("n1", cpu=10_000)])
        sched.add_reservation(self._spec(cpu=8_000))
        res = sched.schedule_round()
        assert res.assignments.get("rsv::rsv-a") == "n1"
        avail = sched.reservations.available()
        assert [s.name for s in avail] == ["rsv-a"]
        # the reserved capacity is invisible to non-owner pods
        sched.enqueue(pod("other", cpu=4_000))
        res = sched.schedule_round()
        assert "other" in res.failures

    def test_owner_pod_allocates_from_reservation(self):
        sched, binds = mk_scheduler([node("n1", cpu=10_000),
                                     node("n2", cpu=10_000)])
        sched.add_reservation(self._spec(cpu=8_000))
        sched.schedule_round()
        rnode = sched.reservations.get("rsv-a").node
        owner = pod("web-1", cpu=6_000, labels={"app": "web"})
        sched.enqueue(owner)
        res = sched.schedule_round()
        # owner lands on the reserved node and charges the reservation
        assert res.assignments["web-1"] == rnode
        spec = sched.reservations.get("rsv-a")
        assert spec.allocated[CPU] == 6_000
        assert spec.owner_pods == ["web-1"]
        # non-owner still can't use the remaining reserved 2k on that node

    def test_pinned_reservation_available_without_solve(self):
        sched, _ = mk_scheduler([node("n1", cpu=10_000)])
        sched.add_reservation(self._spec(node="n1", cpu=8_000))
        sched.enqueue(pod("other", cpu=4_000))
        res = sched.schedule_round()
        assert "other" in res.failures      # capacity charged by pin
        assert sched.reservations.get("rsv-a").node == "n1"

    def test_allocate_once_consumes_reservation(self):
        sched, _ = mk_scheduler([node("n1", cpu=10_000)])
        sched.add_reservation(self._spec(cpu=8_000, allocate_once=True))
        sched.schedule_round()
        sched.enqueue(pod("web-1", cpu=2_000, labels={"app": "web"}))
        res = sched.schedule_round()
        from koordinator_tpu.scheduler.reservations import ReservationPhase

        assert res.assignments["web-1"] == "n1"
        spec = sched.reservations.get("rsv-a")
        assert spec.phase is ReservationPhase.SUCCEEDED
        # consumed: next owner pod schedules on free capacity only
        assert not sched.reservations.available()

    def test_expiration_returns_remainder(self):
        t = [0.0]
        sched, _ = mk_scheduler([node("n1", cpu=10_000)])
        sched.clock = lambda: t[0]
        sched.add_reservation(self._spec(cpu=8_000, ttl=60.0))
        sched.schedule_round()
        assert sched.reservations.available()
        t[0] = 120.0
        sched.enqueue(pod("other", cpu=6_000))
        res = sched.schedule_round()
        # expired: remainder returned, non-owner fits again
        assert res.assignments.get("other") == "n1"

    def test_remove_reservation_frees_capacity(self):
        sched, _ = mk_scheduler([node("n1", cpu=10_000)])
        sched.add_reservation(self._spec(cpu=8_000))
        sched.schedule_round()
        sched.remove_reservation("rsv-a")
        sched.enqueue(pod("other", cpu=6_000))
        res = sched.schedule_round()
        assert res.assignments.get("other") == "n1"

    def test_owner_pod_delete_returns_allocation_not_node_capacity(self):
        # regression: freeing an owner pod must return its drawn vector to
        # the reservation remainder, NOT uncover reserved capacity
        sched, _ = mk_scheduler([node("n1", cpu=10_000)])
        sched.add_reservation(self._spec(cpu=8_000))
        sched.schedule_round()
        sched.enqueue(pod("web-1", cpu=6_000, labels={"app": "web"}))
        sched.schedule_round()
        sched.delete_pod("web-1")
        spec = sched.reservations.get("rsv-a")
        assert spec.allocated[CPU] == 0          # drawn part returned
        # reserved capacity still hidden from non-owners
        sched.enqueue(pod("other", cpu=4_000))
        res = sched.schedule_round()
        assert "other" in res.failures
        # ...but a new owner can draw the full 8k again
        sched.enqueue(pod("web-2", cpu=8_000, labels={"app": "web"}))
        res = sched.schedule_round()
        assert res.assignments.get("web-2") == "n1"

    def test_reapply_available_reservation_is_idempotent(self):
        # regression: upsert over an Available reservation must not
        # double-charge the node via a second reserve-pod
        sched, _ = mk_scheduler([node("n1", cpu=10_000)])
        sched.add_reservation(self._spec(cpu=6_000))
        sched.schedule_round()
        sched.add_reservation(self._spec(cpu=6_000))   # controller resync
        sched.schedule_round()
        avail = sched.reservations.available()
        assert len(avail) == 1 and avail[0].node == "n1"
        # 4k remains genuinely free: exactly one 6k charge on the node
        sched.enqueue(pod("other", cpu=4_000))
        res = sched.schedule_round()
        assert res.assignments.get("other") == "n1"

    def test_pending_reservation_expires_by_ttl(self):
        t = [0.0]
        sched, _ = mk_scheduler([node("n1", cpu=2_000)])
        sched.clock = lambda: t[0]
        sched.add_reservation(self._spec(cpu=50_000, ttl=60.0))  # never fits
        sched.schedule_round()
        t[0] = 120.0
        sched.schedule_round()
        # expired AND purged by the terminal-phase gc
        assert sched.reservations.get("rsv-a") is None
        assert "rsv::rsv-a" not in sched.pending

    def test_pinned_reservation_waits_for_fit(self):
        # a pinned reservation larger than the node's free capacity must
        # stay Pending instead of over-committing the node
        sched, _ = mk_scheduler([node("n1", cpu=2_000)])
        sched.add_reservation(self._spec(node="n1", cpu=8_000))
        sched.enqueue(pod("other", cpu=1_000))
        res = sched.schedule_round()
        assert res.assignments.get("other") == "n1"  # node NOT blocked
        assert not sched.reservations.available()

    def test_allocate_once_frees_fully_with_owner_pod(self):
        # allocate-once consumed by a 2k pod holds the full 8k; the whole
        # charge must free when that pod dies
        sched, _ = mk_scheduler([node("n1", cpu=10_000)])
        sched.add_reservation(self._spec(cpu=8_000, allocate_once=True))
        sched.schedule_round()
        sched.enqueue(pod("web-1", cpu=2_000, labels={"app": "web"}))
        sched.schedule_round()
        sched.delete_pod("web-1")
        sched.enqueue(pod("other", cpu=9_000))
        res = sched.schedule_round()
        assert res.assignments.get("other") == "n1"


class TestMigrationWithReservations:
    _spec = TestReservationRounds._spec

    def test_reservation_first_migration_end_to_end(self):
        """SURVEY 3.4 flow against real scheduler reservations: the
        migration controller secures replacement capacity on another node
        BEFORE evicting, and the replacement pod lands on it."""
        from koordinator_tpu.descheduler.migration import (
            MigrationController, MigrationJob,
        )
        from koordinator_tpu.descheduler.plugins import (
            scheduler_migration_evict_fn, scheduler_reserve_many,
        )

        # the pod binds while only the (soon-to-be-)hot node exists; the
        # cool node joins afterwards — the classic rebalance setup
        sched, _ = mk_scheduler([node("hot", cpu=10_000, usage_cpu=9_000)])
        sched.enqueue(pod("web-1", cpu=4_000, labels={"app": "web"}))
        res = sched.schedule_round()
        src = res.assignments["web-1"]
        assert src == "hot"
        sched.snapshot.upsert_node(node("cool", cpu=10_000))

        ctl = MigrationController(
            reserve_many=scheduler_reserve_many(sched),
            evict_fn=scheduler_migration_evict_fn(sched),
        )
        ctl.submit(MigrationJob(name="j1", pod="web-1", node=src))
        ctl.reconcile()   # arbitrate: reserve on the other node
        job = ctl.jobs["j1"]
        assert job.reservation == "migrate-j1"
        spec = sched.reservations.get("migrate-j1")
        assert spec.node is not None and spec.node != src
        ctl.reconcile()   # running: evict
        assert "web-1" not in sched.bound

        # the replacement pod allocates from the secured reservation
        sched.enqueue(pod("web-1", cpu=4_000, labels={"app": "web"}))
        res = sched.schedule_round()
        assert res.assignments["web-1"] == spec.node
        assert sched.reservations.get("migrate-j1").allocated[CPU] == 4_000

    def test_recreated_reservation_not_credited_by_old_pods(self):
        # generation check: a pod bound through a deleted reservation must
        # not corrupt a later same-named instance's accounting
        sched, _ = mk_scheduler([node("n1", cpu=20_000)])
        sched.add_reservation(self._spec(cpu=8_000))
        sched.schedule_round()
        sched.enqueue(pod("web-1", cpu=4_000, labels={"app": "web"}))
        sched.schedule_round()
        sched.remove_reservation("rsv-a")           # old instance gone
        sched.add_reservation(self._spec(cpu=6_000))  # new instance
        sched.schedule_round()
        new_spec = sched.reservations.get("rsv-a")
        assert new_spec.allocated[CPU] == 0
        sched.delete_pod("web-1")                   # old-instance owner dies
        # the NEW instance's remainder is untouched
        assert sched.reservations.get("rsv-a").allocated[CPU] == 0
        # node accounting consistent: 6k (new rsv) charged, rest free
        sched.enqueue(pod("other", cpu=14_000))
        res = sched.schedule_round()
        assert res.assignments.get("other") == "n1"

    def test_pending_update_refreshes_reserve_pod_requests(self):
        # updating a still-Pending reservation must re-enqueue the reserve
        # pod with the NEW vector, not open a 4k claim backed by a 1k charge
        sched, _ = mk_scheduler([node("n1", cpu=10_000)])
        sched.add_reservation(self._spec(cpu=1_000))
        # don't run a round yet: the reserve-pod sits queued at 1k
        sched.add_reservation(self._spec(cpu=4_000))
        sched.schedule_round()
        spec = sched.reservations.get("rsv-a")
        assert spec.node == "n1"
        # exactly 4k charged: a 7k pod must NOT fit (10k - 4k = 6k free)
        sched.enqueue(pod("big", cpu=7_000))
        res = sched.schedule_round()
        assert "big" in res.failures
        sched.enqueue(pod("ok", cpu=6_000))
        res = sched.schedule_round()
        assert res.assignments.get("ok") == "n1"

    def test_debug_service_reservations_route(self):
        from koordinator_tpu.scheduler.services import DebugService

        sched, _ = mk_scheduler([node("n1", cpu=10_000)])
        svc = DebugService(sched)
        sched.add_reservation(self._spec(cpu=6_000))
        sched.schedule_round()
        status, body = svc.handle("/apis/v1/reservations")
        assert status == 200
        assert body[0]["name"] == "rsv-a"
        assert body[0]["phase"] == "Available"
        assert body[0]["node"] == "n1"

    def test_owner_update_reaches_prepass_cache(self):
        from koordinator_tpu.scheduler.reservations import OwnerMatcher

        sched, _ = mk_scheduler([node("n1", cpu=10_000)])
        sched.add_reservation(self._spec(cpu=8_000, labels={"app": "web"}))
        sched.schedule_round()
        # a db pod isn't an owner: reserved capacity hidden
        sched.enqueue(pod("db-1", cpu=6_000, labels={"app": "db"}))
        res = sched.schedule_round()
        assert "db-1" in res.failures
        # owners widened in place (same requests): db now matches
        spec = self._spec(cpu=8_000)
        spec.owners = [OwnerMatcher(labels={"app": "db"})]
        sched.add_reservation(spec)
        res = sched.schedule_round()
        assert res.assignments.get("db-1") == "n1"
        assert sched.reservations.get("rsv-a").allocated[CPU] == 6_000

    def test_reserve_pod_honors_template_node_selector(self):
        sched, _ = mk_scheduler([
            node("cpu-1", cpu=20_000, labels={"pool": "cpu"}),
            node("gpu-1", cpu=10_000, labels={"pool": "gpu"}),
        ])
        spec = self._spec(cpu=8_000)
        spec.node_selector = {"pool": "gpu"}
        sched.add_reservation(spec)
        sched.schedule_round()
        assert sched.reservations.get("rsv-a").node == "gpu-1"


class TestFineGrainedBind:
    """CPU/device manager integration at bind (nodenumaresource Reserve
    resource_manager.go:357 + deviceshare PreBind device-allocated)."""

    def _managers(self):
        from tests.test_deviceshare import gpu_node
        from tests.test_numa import topo_2numa

        from koordinator_tpu.scheduler.cpu_manager import CPUManager
        from koordinator_tpu.scheduler.device_manager import DeviceManager

        cm = CPUManager()
        cm.register_node("n1", topo_2numa())
        dm = DeviceManager()
        dm.register("gpu", ["n1"], [gpu_node(4)])
        return cm, dm

    def test_lsr_pod_gets_exclusive_cpuset_at_bind(self):
        from koordinator_tpu.api.qos import QoSClass

        cm, dm = self._managers()
        sched, _ = mk_scheduler([node("n1")], cpu_manager=cm,
                                device_manager=dm)
        sched.enqueue(pod("lsr-1", cpu=4_000, qos=int(QoSClass.LSR)))
        sched.enqueue(pod("ls-1", cpu=4_000, qos=int(QoSClass.LS)))
        res = sched.schedule_round()
        assert set(res.assignments) == {"lsr-1", "ls-1"}
        status = sched.resource_status["lsr-1"]["resource-status"]
        assert len(status["cpuset"].split(",")) == 4
        assert "ls-1" not in sched.resource_status   # shared-pool pod
        # release on delete
        sched.delete_pod("lsr-1")
        assert "lsr-1" not in sched.resource_status
        assert cm.node("n1").ref_count.sum() == 0

    def test_gpu_pod_gets_device_allocation_at_bind(self):
        from koordinator_tpu.api.resources import resource_vector

        cm, dm = self._managers()
        from koordinator_tpu.scheduler.snapshot import NodeSpec, PodSpec

        gpu_node_spec = NodeSpec(name="n1", allocatable=resource_vector(
            {"cpu": 16_000, "memory": 65_536, "kubernetes.io/gpu": 400,
             "kubernetes.io/gpu-memory": 81_920 * 4}))
        sched, _ = mk_scheduler([gpu_node_spec], cpu_manager=cm,
                                device_manager=dm)
        sched.enqueue(PodSpec(name="gpu-1", requests=resource_vector(
            {"cpu": 1_000, "memory": 1_024, "kubernetes.io/gpu": 200,
             "kubernetes.io/gpu-memory": 16_384})))
        res = sched.schedule_round()
        assert res.assignments["gpu-1"] == "n1"
        ann = sched.resource_status["gpu-1"]["device-allocated"]
        assert len(ann["gpu"]) == 2    # 200 milli-gpu = 2 whole devices
        sched.delete_pod("gpu-1")
        assert dm.allocate("gpu", "n1", "x", core=400) is not None

    def test_debug_route_exposes_resource_status(self):
        from koordinator_tpu.api.qos import QoSClass
        from koordinator_tpu.scheduler.services import DebugService

        cm, dm = self._managers()
        sched, _ = mk_scheduler([node("n1")], cpu_manager=cm,
                                device_manager=dm)
        svc = DebugService(sched)
        sched.enqueue(pod("lsr-1", cpu=2_000, qos=int(QoSClass.LSR)))
        sched.schedule_round()
        status, body = svc.handle("/apis/v1/resource-status")
        assert status == 200 and "lsr-1" in body

    def test_preemption_releases_victim_fine_grained_allocs(self):
        from koordinator_tpu.api.qos import QoSClass

        cm, dm = self._managers()
        sched, _ = mk_scheduler(
            [node("n1", cpu=8_000)], cpu_manager=cm, device_manager=dm,
            enable_preemption=True, preempt_fn=lambda pod, node: True)
        sched.enqueue(pod("lsr-low", cpu=6_000, qos=int(QoSClass.LSR),
                          priority=3_000))
        sched.schedule_round()
        assert cm.node("n1").ref_count.sum() == 6
        sched.enqueue(pod("prod-high", cpu=6_000, priority=9_500))
        sched.schedule_round()   # PostFilter: evict lsr-low, nominate
        assert "lsr-low" not in sched.bound
        # victim's exclusive cpuset released with the eviction
        assert cm.node("n1").ref_count.sum() == 0
        assert "lsr-low" not in sched.resource_status

    def test_restart_replay_restores_pinned_cpus_and_minors(self):
        from koordinator_tpu.scheduler.scheduler import BoundPod

        cm, dm = self._managers()
        sched, _ = mk_scheduler([node("n1")], cpu_manager=cm,
                                device_manager=dm)
        # informer replay: an LSR pod pinned to cpus 0-3 and a GPU pod
        # holding minors 0-1 were running before the restart
        sched.add_bound_pod(
            BoundPod(name="old-lsr", node="n1",
                     requests=resource_vector(cpu=4_000, memory=1_024),
                     priority=9_000),
            resource_status={"resource-status": {"cpuset": "0,1,2,3"}})
        sched.add_bound_pod(
            BoundPod(name="old-gpu", node="n1",
                     requests=resource_vector(cpu=1_000, memory=1_024),
                     priority=9_000),
            resource_status={"device-allocated": {"gpu": [
                {"minor": 0, "resources": {"core": 100, "memory": 81_920}},
                {"minor": 1, "resources": {"core": 100, "memory": 81_920}},
            ]}})
        assert cm.node("n1").ref_count[:4].sum() == 4
        # a new exclusive allocation avoids the replayed cores
        cpus = cm.allocate("n1", "new-lsr", 4)
        assert cpus is not None and not set(cpus) & {0, 1, 2, 3}
        # a 3-whole GPU ask fails while minors 0-1 are replayed as held
        assert dm.allocate("gpu", "n1", "new-gpu", core=300) is None
        sched.remove_bound_pod("old-gpu")
        assert dm.allocate("gpu", "n1", "new-gpu", core=300) is not None

    def test_koordlet_nrt_annotation_registers_topology(self):
        """koordlet NodeTopologyReporter annotations -> scheduler CPUManager
        (the NRT CRD loop: nodetopo report to topology_options consume)."""
        from koordinator_tpu.api.qos import QoSClass
        from koordinator_tpu.koordlet.nodetopo import NodeTopology, NUMAZone
        from koordinator_tpu.koordlet.system import procfs
        from koordinator_tpu.scheduler.cpu_manager import (
            CPUManager, register_node_from_annotations,
        )

        cpus = tuple(
            procfs.CPUInfo(cpu=i, core=i // 2, socket=0, node=i // 4)
            for i in range(8))
        topo = NodeTopology(
            zones=(NUMAZone("node0", 4_000, 1 << 30, (0, 1, 2, 3)),
                   NUMAZone("node1", 4_000, 1 << 30, (4, 5, 6, 7))),
            cpu_topology=cpus)
        cm = CPUManager()
        assert register_node_from_annotations(
            cm, "n1", topo.to_annotations())
        sched, _ = mk_scheduler([node("n1")], cpu_manager=cm)
        sched.enqueue(pod("lsr-1", cpu=2_000, qos=int(QoSClass.LSR)))
        sched.schedule_round()
        status = sched.resource_status["lsr-1"]["resource-status"]
        assert len(status["cpuset"].split(",")) == 2
        assert not register_node_from_annotations(cm, "nx", {})

    def test_restore_rejects_malformed_and_stale_annotations(self):
        from koordinator_tpu.scheduler.scheduler import BoundPod

        cm, dm = self._managers()
        sched, _ = mk_scheduler([node("n1")], cpu_manager=cm,
                                device_manager=dm)
        # range-form cpuset parses; stale cpu ids / bad minors are skipped
        sched.add_bound_pod(
            BoundPod(name="ranged", node="n1",
                     requests=resource_vector(cpu=2_000, memory=512)),
            resource_status={"resource-status": {"cpuset": "0-1"}})
        assert cm.node("n1").ref_count[:2].sum() == 2
        sched.add_bound_pod(
            BoundPod(name="stale", node="n1",
                     requests=resource_vector(cpu=2_000, memory=512)),
            resource_status={
                "resource-status": {"cpuset": "500-501"},       # beyond topo
                "device-allocated": {"gpu": [{"minor": 99}],    # beyond devs
                                     "fpga": [{"minor": 0}]}})  # unknown type
        assert "stale" not in sched.resource_status
        # replaying the same GPU pod twice must not double-charge
        grant = {"device-allocated": {"gpu": [
            {"minor": 0, "resources": {"core": 100, "memory": 81_920}}]}}
        for _ in range(2):
            sched.add_bound_pod(
                BoundPod(name="gpu-replay", node="n1",
                         requests=resource_vector(cpu=1_000, memory=512)),
                resource_status=grant)
        sched.remove_bound_pod("gpu-replay")
        assert dm.allocate("gpu", "n1", "x", core=400) is not None

    def test_node_resync_preserves_exclusive_cpuset(self):
        # heartbeat re-registration of the same topology must not wipe
        # live allocations (double-grant of exclusive cores)
        from koordinator_tpu.ops.numa import CPUTopology

        import numpy as _np

        cm, dm = self._managers()
        topo = cm.node("n1").topology
        cpus = cm.allocate("n1", "lsr-a", 4)
        assert cpus is not None
        cm.register_node("n1", topo)             # identical re-sync
        assert cm.node("n1").ref_count.sum() == 4
        # a changed topology carries valid allocations over
        cm.register_node("n1", CPUTopology.build(
            _np.asarray(topo.core_of), _np.asarray(topo.numa_of),
            _np.asarray(topo.socket_of)), max_ref=2)
        assert cm.node("n1").allocations["lsr-a"].cpus == cpus
        assert cm.node("n1").ref_count.sum() == 4

    def test_device_inventory_shrink_keeps_records_filters_views(self):
        """An inventory shrink must not destroy allocation records (a
        transient clear + heartbeat restore would otherwise free devices
        still held by bound pods); instead the VIEWS filter to live
        minors — annotations report only existing devices, release
        doesn't crash, and a restored inventory re-commits the grant."""
        from koordinator_tpu.scheduler.device_manager import DeviceManager

        dm = DeviceManager()
        full = [{"core": 100, "memory": 0, "group": 0} for _ in range(5)]
        dm.register_node_devices("gpu", "n0", full)
        assert dm.allocate("gpu", "n0", "p", core=500) is not None
        dm.register_node_devices("gpu", "n0", full[:2])
        # the RECORD keeps all five minors; the annotation view filters
        allocs = dm._allocs[("p", "n0")]
        assert sorted(m for a in allocs for m in a.minors) == [0, 1, 2, 3, 4]
        ann = dm.device_allocated_annotation("n0", "p")
        assert sorted(g["minor"] for g in ann["gpu"]) == [0, 1]
        # inventory returns: the held minors re-commit, so a new pod
        # cannot be granted devices p still uses
        dm.register_node_devices("gpu", "n0", full)
        state = dm.state("gpu")
        # every device's core capacity is committed again — a new pod
        # cannot be granted what p holds
        assert int(np.asarray(state.free)[..., 0].sum()) == 0
        # release frees only live minors and doesn't crash
        dm.release("n0", "p")
        assert dm.allocate("gpu", "n0", "q", core=200) is not None


def test_overuse_revoke_in_round_loop():
    """quota_overuse_revoke.go through the rounds: runtime shrinks after
    admission, the over-used quota's least-important pod is revoked past
    the delay, and the freed headroom admits the other quota's pod."""
    t = [0.0]
    total = resource_vector(cpu=16_000, memory=131_072).astype(np.int64)
    tree = QuotaTree(total)
    mx = np.full(R, UNBOUNDED, np.int64)
    mx[CPU] = 16_000
    for q in ("a", "b"):
        tree.add(q, min=np.zeros(R, np.int64), max=mx)
    sched, _ = mk_scheduler([node("n1", cpu=16_000)], quota_tree=tree,
                            clock=lambda: t[0])
    revoked = []
    sched.enable_overuse_revoke(
        revoke_fn=lambda p, q: revoked.append((p, q)), delay_evict_sec=5.0)

    # quota a takes nearly everything while b is idle
    sched.enqueue(pod("a-low", cpu=10_000, quota="a", priority=3_000))
    sched.enqueue(pod("a-high", cpu=4_000, quota="a", priority=9_000))
    res = sched.schedule_round()
    assert {"a-low", "a-high"} <= set(res.assignments)

    # b starts demanding: its pod can't fit (2k node free), stays pending,
    # and its request shrinks a's runtime share below a's used
    sched.enqueue(pod("b-1", cpu=8_000, quota="b", priority=9_000))
    res = sched.schedule_round()    # monitor arms (fresh runtime computed)
    assert "b-1" in res.failures
    assert np.any(tree.nodes["a"].used > tree.nodes["a"].runtime)

    t[0] = 10.0                     # past delay_evict_sec
    res = sched.schedule_round()
    # least-important overshoot pod revoked; b's pod admitted
    assert ("a-low", "a") in revoked
    assert "a-low" not in sched.bound
    assert res.assignments.get("b-1") == "n1"
    assert "a-high" in sched.bound  # the important pod survives


def test_overuse_revoke_honors_pdb_budget():
    from koordinator_tpu.scheduler.scheduler import PdbRecord

    t = [0.0]
    total = resource_vector(cpu=16_000, memory=131_072).astype(np.int64)
    tree = QuotaTree(total)
    mx = np.full(R, UNBOUNDED, np.int64)
    mx[CPU] = 16_000
    for q in ("a", "b"):
        tree.add(q, min=np.zeros(R, np.int64), max=mx)
    sched, _ = mk_scheduler([node("n1", cpu=16_000)], quota_tree=tree,
                            clock=lambda: t[0])
    revoked = []
    sched.enable_overuse_revoke(
        revoke_fn=lambda p, q: revoked.append(p), delay_evict_sec=5.0)
    sched.register_pdb(PdbRecord(name="protect-a",
                                 selector={"app": "a"}, allowed=0))
    sched.enqueue(pod("a-low", cpu=14_000, quota="a", priority=3_000,
                      labels={"app": "a"}))
    sched.schedule_round()
    sched.enqueue(pod("b-1", cpu=8_000, quota="b", priority=9_000))
    sched.schedule_round()
    t[0] = 10.0
    sched.schedule_round()
    # PDB exhausted: the overshoot pod survives the revoke
    assert revoked == []
    assert "a-low" in sched.bound


def test_overuse_revoke_selects_around_pdb_protected_pod():
    """A PDB-protected lowest-priority pod must not permanently block
    revocation: the kernel selects the evictable alternative instead."""
    from koordinator_tpu.scheduler.scheduler import PdbRecord

    t = [0.0]
    total = resource_vector(cpu=16_000, memory=131_072).astype(np.int64)
    tree = QuotaTree(total)
    mx = np.full(R, UNBOUNDED, np.int64)
    mx[CPU] = 16_000
    for q in ("a", "b"):
        tree.add(q, min=np.zeros(R, np.int64), max=mx)
    sched, _ = mk_scheduler([node("n1", cpu=16_000)], quota_tree=tree,
                            clock=lambda: t[0])
    revoked = []
    sched.enable_overuse_revoke(
        revoke_fn=lambda p, q: revoked.append(p), delay_evict_sec=5.0)
    sched.register_pdb(PdbRecord(name="protect-low",
                                 selector={"tier": "low"}, allowed=0))
    sched.enqueue(pod("a-low", cpu=7_000, quota="a", priority=3_000,
                      labels={"tier": "low"}))
    sched.enqueue(pod("a-mid", cpu=7_000, quota="a", priority=6_000))
    sched.schedule_round()
    sched.enqueue(pod("b-1", cpu=8_000, quota="b", priority=9_000))
    sched.schedule_round()
    t[0] = 10.0
    res = sched.schedule_round()
    # the unprotected pod was chosen even though a-low is less important
    assert revoked == ["a-mid"]
    assert "a-low" in sched.bound
    assert res.assignments.get("b-1") == "n1"


def test_overuse_revoke_skips_uncurable_quota_with_blocked_pod():
    """When the overshoot is pinned by a PDB-blocked pod (eviction cannot
    cure the quota), no collateral eviction happens; the quota retries
    once budgets recover."""
    from koordinator_tpu.scheduler.scheduler import PdbRecord

    t = [0.0]
    total = resource_vector(cpu=16_000, memory=131_072).astype(np.int64)
    tree = QuotaTree(total)
    mx = np.full(R, UNBOUNDED, np.int64)
    mx[CPU] = 16_000
    for q in ("a", "b"):
        tree.add(q, min=np.zeros(R, np.int64), max=mx)
    sched, _ = mk_scheduler([node("n1", cpu=16_000)], quota_tree=tree,
                            clock=lambda: t[0])
    revoked = []
    sched.enable_overuse_revoke(
        revoke_fn=lambda p, q: revoked.append(p), delay_evict_sec=5.0)
    sched.register_pdb(PdbRecord(name="protect-big",
                                 selector={"tier": "big"}, allowed=0))
    # the protected pod ALONE overshoots whatever runtime a will get;
    # evicting the small pods cannot cure the quota
    sched.enqueue(pod("a-big", cpu=12_000, quota="a", priority=3_000,
                      labels={"tier": "big"}))
    sched.enqueue(pod("a-small", cpu=2_000, quota="a", priority=6_000))
    sched.schedule_round()
    sched.enqueue(pod("b-1", cpu=8_000, quota="b", priority=9_000))
    sched.schedule_round()
    t[0] = 10.0
    sched.schedule_round()
    assert revoked == []                  # no pointless collateral eviction
    assert {"a-big", "a-small"} <= set(sched.bound)


def test_node_flap_preserves_device_grants():
    """A node flap (NODE_REMOVE then re-upsert with the same inventory,
    e.g. a kubelet restart while pods keep running) must not free
    devices a bound pod still holds: records survive the removal and
    re-commit on the rebuild, so a second pod cannot be granted them."""
    from koordinator_tpu.scheduler.device_manager import DeviceManager

    dm = DeviceManager()
    inv = [{"core": 100, "memory": 0, "group": 0} for _ in range(2)]
    dm.register_node_devices("gpu", "n0", inv)
    assert dm.allocate("gpu", "n0", "p", core=200) == [0, 1]
    dm.remove_node("n0")
    assert dm.state("gpu") is None          # inventory rows gone
    dm.register_node_devices("gpu", "n0", inv)
    # held devices re-committed: the flap cannot double-grant
    assert dm.allocate("gpu", "n0", "q", core=200) is None
    ann = dm.device_allocated_annotation("n0", "p")
    assert sorted(g["minor"] for g in ann["gpu"]) == [0, 1]
    # pod release purges the record even while the node is absent
    dm.remove_node("n0")
    dm.release("n0", "p")
    dm.register_node_devices("gpu", "n0", inv)
    assert dm.allocate("gpu", "n0", "q", core=200) == [0, 1]
