"""Multi-tenant round pipeline (ISSUE 11): the tenancy subsystem's
acceptance suite.

The contracts under test:

- **pipeline bit-identity**: two rounds per tenant driven through the
  pipelined host/device split (tenant B's solve dispatched before
  tenant A's commit) produce the SAME binds and the SAME quota charges
  as the serial single-tenant-at-a-time path — including the
  incremental dirty path (cycle 2 re-scores only the delta) and the
  8-way sharded mesh;
- **tenant-axis batching**: the one-dispatch ``vmap``-batched
  select+pass1 program is bit-identical per tenant to the serial
  solves;
- **degraded isolation**: tenant A's stale sync feed suspends ONLY A's
  BE admission — B keeps binding BE pods through the same cycle;
- **weighted fairness**: under sustained overload from a loadgen
  multi-tenant trace, admitted shares converge to weight fractions
  (deficit round robin);
- **surfaces**: /debug/tenants parity across DebugService and the HTTP
  gateway, per-half tenant-stamped flight records, per-tenant SLO
  label filtering.

Compile budget: every front shares ONE SolverKit per mesh flavor
(module fixtures), shapes are tiny, and the pipelined/serial pairs
replay identical seeded inputs.
"""

import dataclasses
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))

import loadgen  # noqa: E402  (tools/loadgen.py; no JAX at module scope)


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def kit_off():
    """One single-device SolverKit shared by every unsharded front in
    this module (T tenants already share one kit per front; the tests
    extend the sharing across fronts so the module compiles each
    program once)."""
    from koordinator_tpu.scheduler.solver_kit import SolverKit

    return SolverKit(mesh="off")


def _quota_tree(cpu_max: int = 60_000):
    from koordinator_tpu.api.resources import NUM_RESOURCE_DIMS
    from koordinator_tpu.quota.tree import UNBOUNDED, QuotaTree

    total = np.zeros(NUM_RESOURCE_DIMS, np.int64)
    total[0] = 200_000
    tree = QuotaTree(total)
    mx = np.full(NUM_RESOURCE_DIMS, UNBOUNDED, np.int64)
    mx[0] = cpu_max
    tree.add("q", min=np.zeros(NUM_RESOURCE_DIMS, np.int64), max=mx)
    return tree


def _make_front(kit=None, tenants=("a", "b"), weights=None, quotas=False,
                **front_kw):
    from koordinator_tpu.scheduler.tenancy import TenantScheduler, TenantSpec

    front_kw.setdefault("cycle_pod_budget", 1 << 20)
    front = TenantScheduler(solver_kit=kit, **front_kw)
    for i, name in enumerate(tenants):
        front.add_tenant(
            TenantSpec(name=name,
                       weight=(weights[i] if weights else 1.0),
                       node_capacity=16),
            batch_solver_threshold=1,
            quota_tree=_quota_tree() if quotas else None)
    return front


def _feed_nodes(scheduler, n=10, seed=3, batch_cpu=0):
    from koordinator_tpu.api.resources import resource_vector
    from koordinator_tpu.scheduler.snapshot import NodeSpec

    rng = np.random.default_rng(seed)
    for i in range(n):
        scheduler.snapshot.upsert_node(NodeSpec(
            name=f"n{i}",
            allocatable=resource_vector(
                cpu=int(rng.integers(8_000, 32_000)),
                memory=int(rng.integers(16_384, 65_536)),
                **({"batch_cpu": batch_cpu} if batch_cpu else {})),
            usage=resource_vector(cpu=int(rng.integers(0, 2_000)),
                                  memory=int(rng.integers(0, 4_096)))))


def _pod(seed, name, quota=None):
    from koordinator_tpu.api.resources import resource_vector
    from koordinator_tpu.scheduler.snapshot import PodSpec

    rng = np.random.default_rng(seed)
    return PodSpec(
        name=name,
        requests=resource_vector(cpu=int(rng.integers(200, 3_000)),
                                 memory=int(rng.integers(256, 8_192))),
        priority=int(rng.integers(3_000, 9_999)),
        quota=quota)


def _seed_tenants(front, pods_per_tenant=6, base=0, quota=None):
    for ti, tenant in enumerate(front.tenants()):
        _feed_nodes(tenant.scheduler, seed=11 + ti)
        for j in range(pods_per_tenant):
            tenant.scheduler.enqueue(_pod(
                base * 10_000 + ti * 1_000 + j,
                f"p{base}-{j}", quota=quota))


def _delta_tenants(front, base):
    """A small steady-state delta per tenant: three new pods + one
    node's usage refresh (keeps the dirty fraction under the
    incremental threshold next cycle)."""
    from koordinator_tpu.api.resources import resource_vector

    for ti, tenant in enumerate(front.tenants()):
        sched = tenant.scheduler
        for j in range(3):
            sched.enqueue(_pod(base * 10_000 + ti * 1_000 + 500 + j,
                               f"p{base}-d{j}",
                               quota=("q" if sched.quota_tree else None)))
        spec = sched.snapshot.node_specs["n1"]
        sched.snapshot.upsert_node(dataclasses.replace(
            spec, usage=resource_vector(cpu=700 + 13 * ti, memory=2_048)))


def _binds(results):
    return {name: dict(r.assignments) for name, r in results.items()}


def _quota_used(front):
    out = {}
    for t in front.tenants():
        tree = t.scheduler.quota_tree
        if tree is not None:
            out[t.name] = np.asarray(tree.nodes["q"].used).tolist()
    return out


def _assert_no_overcommit(front):
    for t in front.tenants():
        st = t.scheduler.snapshot.state
        ok = (np.asarray(st.node_requested)
              <= np.asarray(st.node_allocatable)).all(axis=-1)
        assert ok[np.asarray(st.node_valid)].all(), \
            f"tenant {t.name} overcommitted"


# ---------------------------------------------------------------------------
# pipeline bit-identity
# ---------------------------------------------------------------------------


class TestPipelineBitIdentity:
    def test_two_round_overlap_matches_serial_incl_incremental(self, kit_off):
        """Two cycles, two tenants, quota-charged: the pipelined cycle
        (B's device solve dispatched before A's host commit) must bind
        the same pods to the same nodes and charge the same quota as
        serial single-tenant-at-a-time rounds — and cycle 2 must
        actually take the incremental dirty path."""
        serial = _make_front(kit_off, quotas=True, pipeline=False,
                             batch_tenant_axis=False)
        piped = _make_front(kit_off, quotas=True, pipeline=True,
                            batch_tenant_axis=False)
        for front in (serial, piped):
            _seed_tenants(front, base=1, quota="q")
            # small cluster: bind deltas are a large node FRACTION;
            # force the incremental path so cycle 2 exercises the merge
            for t in front.tenants():
                t.scheduler.incremental_dirty_threshold = 1.0
        r_ser1 = serial.schedule_cycle()
        r_pip1 = piped.schedule_cycle()
        assert serial.last_mode == "serial"
        assert piped.last_mode == "pipelined"
        assert _binds(r_ser1) == _binds(r_pip1)
        assert _quota_used(serial) == _quota_used(piped)

        _delta_tenants(serial, base=2)
        _delta_tenants(piped, base=2)
        r_ser2 = serial.schedule_cycle()
        r_pip2 = piped.schedule_cycle()
        assert _binds(r_ser2) == _binds(r_pip2)
        assert _quota_used(serial) == _quota_used(piped)
        _assert_no_overcommit(piped)
        # the steady-state delta actually rode the incremental path
        for t in piped.tenants():
            assert t.scheduler.last_solve_path == "incremental", \
                t.scheduler.last_solve_path

    def test_pipelined_matches_serial_on_sharded_mesh(self):
        """The same two-cycle pipelined-vs-serial identity with every
        tenant's solve on the 8-way nodes-axis mesh (shard_min_nodes=0
        engages sharding at the 16-row test capacity)."""
        from koordinator_tpu.scheduler.solver_kit import SolverKit

        kit_mesh = SolverKit(mesh="auto", shard_min_nodes=0)
        assert kit_mesh.shards == 8    # the virtual 8-device platform
        serial = _make_front(kit_mesh, pipeline=False,
                             batch_tenant_axis=False)
        piped = _make_front(kit_mesh, pipeline=True,
                            batch_tenant_axis=False)
        for front in (serial, piped):
            _seed_tenants(front, base=3)
            for t in front.tenants():
                t.scheduler.incremental_dirty_threshold = 1.0
                assert kit_mesh.sharding_active_for(
                    t.scheduler.snapshot.capacity)
        assert _binds(serial.schedule_cycle()) == \
            _binds(piped.schedule_cycle())
        _delta_tenants(serial, base=4)
        _delta_tenants(piped, base=4)
        assert _binds(serial.schedule_cycle()) == \
            _binds(piped.schedule_cycle())
        _assert_no_overcommit(piped)
        for t in piped.tenants():
            assert t.scheduler.last_solve_path == "incremental"


class TestTenantAxisBatch:
    def test_batched_cycle_matches_serial_per_tenant(self, kit_off):
        """The ONE vmapped tenant-axis program (stacked (T, N, R)
        states, broadcast config) binds exactly what per-tenant serial
        solves bind, quota charges included."""
        serial = _make_front(kit_off, quotas=True, pipeline=False,
                             batch_tenant_axis=False)
        batched = _make_front(kit_off, quotas=True,
                              batch_tenant_axis=True)
        for front in (serial, batched):
            _seed_tenants(front, pods_per_tenant=8, base=5, quota="q")
        r_ser = serial.schedule_cycle()
        r_bat = batched.schedule_cycle()
        assert batched.last_mode == "batched"
        for t in batched.tenants():
            assert t.scheduler.last_solve_path == "tenant_batched"
        assert _binds(r_ser) == _binds(r_bat)
        assert _quota_used(serial) == _quota_used(batched)
        _assert_no_overcommit(batched)

    def test_misaligned_cycle_falls_back_to_pipelined(self, kit_off):
        """A gang in one tenant's round breaks shape alignment: the
        cycle falls back to the pipelined per-tenant dispatch and still
        schedules everything."""
        from koordinator_tpu.scheduler.scheduler import GangRecord

        front = _make_front(kit_off, batch_tenant_axis=True)
        _seed_tenants(front, pods_per_tenant=4, base=6)
        sched_a = front.tenant("a").scheduler
        sched_a.register_gang(GangRecord(name="g1", min_member=2))
        for j in range(2):
            pod = _pod(66_000 + j, f"g1-{j}")
            pod.gang = "g1"
            sched_a.enqueue(pod)
        results = front.schedule_cycle()
        assert front.last_mode == "pipelined"
        assert len(results) == 2
        assert any("g1-" in p for p in results["a"].assignments)


# ---------------------------------------------------------------------------
# isolation + fairness
# ---------------------------------------------------------------------------


class TestDegradedIsolation:
    def test_one_stale_tenant_suspends_only_its_own_be_admission(
            self, kit_off):
        """Tenant A's sync feed stalls past the staleness threshold;
        the same cycle must flip ONLY A into degraded mode: A's BE pod
        is suspended (held pending), B's BE pod binds."""
        from koordinator_tpu.api.qos import QoSClass
        from koordinator_tpu.api.resources import resource_vector
        from koordinator_tpu.scheduler.snapshot import PodSpec
        from koordinator_tpu.scheduler.tenancy import (
            TenantScheduler,
            TenantSpec,
        )

        now = [100.0]
        front = TenantScheduler(solver_kit=kit_off,
                                batch_tenant_axis=False)
        for name in ("a", "b"):
            front.add_tenant(
                TenantSpec(name=name, node_capacity=16),
                batch_solver_threshold=1,
                staleness_threshold_sec=5.0,
                clock=lambda: now[0])
            _feed_nodes(front.tenant(name).scheduler, batch_cpu=8_000,
                        seed=21)
        # A's feed last spoke long ago; B's is fresh
        front.tenant("a").scheduler.snapshot.mark_sync(10.0)
        front.tenant("b").scheduler.snapshot.mark_sync(99.5)
        for name in ("a", "b"):
            front.tenant(name).scheduler.enqueue(PodSpec(
                name="be-pod",
                requests=resource_vector(batch_cpu=500),
                qos=int(QoSClass.BE)))
        results = front.schedule_cycle()
        a, b = front.tenant("a").scheduler, front.tenant("b").scheduler
        assert a.degraded and not b.degraded
        assert a.last_suspended == 1
        assert "be-pod" in a.pending            # held, not failed
        assert "be-pod" in results["b"].assignments
        # isolation the other way too: A recovering exits degraded
        # without touching B
        a.snapshot.mark_sync(now[0])
        front.schedule_cycle()
        assert not a.degraded and not b.degraded


class TestWeightedFairness:
    def test_admission_shares_converge_under_loadgen_overload(
            self, kit_off):
        """Sustained overload from a 3-tenant loadgen trace: admitted
        shares must converge to the weight fractions (1:1:2)."""
        from koordinator_tpu.api.resources import resource_vector
        from koordinator_tpu.scheduler.snapshot import NodeSpec, PodSpec

        cfg = dataclasses.replace(
            loadgen.LoadGenConfig(seed=9), tenants=3, duration_s=120.0,
            arrival_rate=3.0, gang_rate=0.0, node_flap_rate=0.0,
            quota_churn_rate=0.0, pod_lifetime_s=1e9, quotas=0)
        events = loadgen.generate_trace(cfg)
        by_tenant = {name: [] for name in cfg.tenant_names()}
        for e in events:
            if e.kind == loadgen.POD_ADD:
                by_tenant[e.payload["tenant"]].append(e)
        assert all(len(v) > 200 for v in by_tenant.values())

        front = _make_front(kit_off, tenants=cfg.tenant_names(),
                            weights=(1.0, 1.0, 2.0),
                            batch_tenant_axis=False,
                            cycle_pod_budget=32)
        for name, adds in by_tenant.items():
            sched = front.tenant(name).scheduler
            # a fat node wall so admission (not capacity) is the bound
            for i in range(4):
                sched.snapshot.upsert_node(NodeSpec(
                    name=f"n{i}", allocatable=resource_vector(
                        cpu=10_000_000, memory=10_000_000)))
            for e in adds:
                sched.enqueue(PodSpec(
                    name=e.name,
                    requests=resource_vector(cpu=e.payload["cpu"],
                                             memory=e.payload["memory"]),
                    priority=int(e.payload["priority"])))
        for _ in range(10):
            front.schedule_cycle()
        admitted = {t.name: t.admitted_total for t in front.tenants()}
        total = sum(admitted.values())
        assert total > 0
        shares = {k: v / total for k, v in admitted.items()}
        assert shares["t0"] == pytest.approx(0.25, abs=0.03)
        assert shares["t1"] == pytest.approx(0.25, abs=0.03)
        assert shares["t2"] == pytest.approx(0.50, abs=0.03)
        # overload persisted: the budget, not the backlog, was binding
        assert all(len(t.scheduler.pending) > 0 for t in front.tenants())
        # and the report serves the same observables
        report = front.tenants_report()
        t2 = next(d for d in report["tenants"] if d["name"] == "t2")
        assert t2["share_target"] == pytest.approx(0.5)


# ---------------------------------------------------------------------------
# surfaces
# ---------------------------------------------------------------------------


class TestSurfaces:
    def test_debug_tenants_parity_across_both_surfaces(self, kit_off):
        """/debug/tenants serves the SAME body through the DebugService
        and the HTTP gateway (shared debug_tenants_body builder), and a
        single-tenant scheduler answers a typed 501 on both."""
        import json
        import urllib.request

        from koordinator_tpu.scheduler import ClusterSnapshot, Scheduler
        from koordinator_tpu.scheduler.services import DebugService
        from koordinator_tpu.transport.http_gateway import HttpGateway

        front = _make_front(kit_off, batch_tenant_axis=False)
        _seed_tenants(front, pods_per_tenant=2, base=7)
        front.schedule_cycle()
        service = DebugService(front.tenant("a").scheduler)
        status, body = service.handle("/debug/tenants")
        assert status == 200
        assert {d["name"] for d in body["tenants"]} == {"a", "b"}
        assert body["cycle"]["mode"] == "pipelined"

        gateway = HttpGateway(scheduler=front.tenant("b").scheduler)
        gateway.start()
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{gateway.port}/debug/tenants"
            ) as resp:
                gw_body = json.loads(resp.read())
        finally:
            gateway.stop()
        assert gw_body == body

        lone = Scheduler(ClusterSnapshot(capacity=16), solver_kit=kit_off)
        assert DebugService(lone).handle("/debug/tenants")[0] == 501

    def test_flight_records_stamp_tenant_and_half(self, kit_off):
        """A pipelined cycle leaves one solve-half and one commit-half
        record per tenant, tenant-stamped; serial schedule_round keeps
        half='round'."""
        front = _make_front(kit_off, batch_tenant_axis=False)
        _seed_tenants(front, pods_per_tenant=2, base=8)
        front.schedule_cycle()
        for t in front.tenants():
            halves = [(r.tenant, r.half)
                      for r in t.scheduler.flight_recorder.records]
            assert (t.name, "solve") in halves
            assert (t.name, "commit") in halves
        # /debug/rounds carries the stamps
        from koordinator_tpu.scheduler.services import debug_rounds_body

        doc = debug_rounds_body(front.tenant("a").scheduler, 8)
        assert {r["half"] for r in doc["rounds"]} == {"solve", "commit"}
        assert {r["tenant"] for r in doc["rounds"]} == {"a"}

    def test_scheduling_latency_carries_tenant_label(self, kit_off):
        from koordinator_tpu import metrics

        front = _make_front(kit_off, batch_tenant_axis=False)
        _seed_tenants(front, pods_per_tenant=2, base=9)
        front.schedule_cycle()
        label_sets = [dict(labels) for labels, *_ in
                      metrics.scheduling_latency.state()]
        tenants = {ls.get("tenant") for ls in label_sets
                   if "tenant" in ls}
        assert {"a", "b"} <= tenants
        # per-tenant enqueue/admission counters too
        assert metrics.pods_enqueued_total.value(
            labels={"tenant": "a"}) > 0
        assert metrics.tenant_admitted.value(labels={"tenant": "a"}) > 0

    def test_tenant_slo_spec_slices_by_label(self):
        """The per-tenant p99 SLO only counts its own tenant's
        observations: tenant A's slow solves must not burn tenant B's
        budget."""
        from koordinator_tpu import metrics as m
        from koordinator_tpu.slo_monitor import SloMonitor, tenant_slo_specs

        class FakeClock:
            def __init__(self):
                self.t = 1_000.0

            def __call__(self):
                return self.t

        reg = m.Registry("t11")
        h = reg.histogram("scheduling_duration_seconds",
                          buckets=(0.1, 0.2, 1.0))
        clock = FakeClock()
        specs = tenant_slo_specs(["a", "b"], latency_threshold_s=0.2)
        specs = [dataclasses.replace(
            s, metric="t11_scheduling_duration_seconds") for s in specs]
        mon = SloMonitor(specs=specs, registries=(reg,), clock=clock)
        h.observe(0.9, labels={"phase": "Solve", "tenant": "a"})
        h.observe(0.05, labels={"phase": "Solve", "tenant": "b"})
        mon.sample_once()
        h.observe(0.9, labels={"phase": "Solve", "tenant": "a"})
        h.observe(0.05, labels={"phase": "Solve", "tenant": "b"})
        clock.t += 10.0
        report = mon.tick()
        by_name = {d["name"]: d for d in report["slos"]}
        assert by_name["tenant_a_latency_p99"]["windows"]["fast"][
            "bad_fraction"] == pytest.approx(1.0)
        assert by_name["tenant_b_latency_p99"]["windows"]["fast"][
            "bad_fraction"] == pytest.approx(0.0)


class TestSharedSolverKit:
    def test_tenants_share_one_jit_cache(self, kit_off):
        """T tenants on one front reuse the SAME instrumented jit
        entries — the multiplexing that keeps N clusters from compiling
        N copies of the solver."""
        front = _make_front(kit_off, batch_tenant_axis=False)
        a = front.tenant("a").scheduler
        b = front.tenant("b").scheduler
        assert a.kit is b.kit is kit_off
        assert a.kit.pass1 == b.kit.pass1
        assert a.kit.solve == b.kit.solve

    def test_standalone_scheduler_builds_its_own_kit(self):
        from koordinator_tpu.scheduler import ClusterSnapshot, Scheduler

        s1 = Scheduler(ClusterSnapshot(capacity=16))
        s2 = Scheduler(ClusterSnapshot(capacity=16))
        assert s1.kit is not s2.kit     # the pre-tenancy default
