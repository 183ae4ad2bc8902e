"""Forecast plane (ISSUE 15): predictor edge cases, the device-resident
plane, the predictive-admission solve entries (sharded twin included),
proactive rebalance, and the reactive-vs-predictive A/B.

The predictor edge cases are the ones the closed loop now DEPENDS on:
a cold-start pod contributing nonzero would shrink BE capacity for
workloads with no history; an empty bank producing NaN would poison
the admission reserve tensor; a percentile that loses monotonicity
across decay renormalization would let a stale peak outrank a fresh
one.
"""

import time

import numpy as np
import pytest

from koordinator_tpu.api.resources import (
    NUM_RESOURCE_DIMS,
    ResourceDim,
    resource_vector,
)
from koordinator_tpu.forecast import FORECAST_MODES, kernels
from koordinator_tpu.forecast.plane import ForecastPlane
from koordinator_tpu.prediction.histogram import (
    HistogramBank,
    add_samples,
    default_cpu_buckets,
    percentile,
)
from koordinator_tpu.prediction.predictor import pod_reclaimable
from koordinator_tpu.scheduler.solver_kit import SolverKit
from koordinator_tpu.state.cluster_state import ClusterState, MAX_QUANTITY

R = NUM_RESOURCE_DIMS
CPU = ResourceDim.CPU
MEM = ResourceDim.MEMORY


# ---------------------------------------------------------------------------
# predictor edge cases the loop depends on
# ---------------------------------------------------------------------------


class TestPredictorEdges:
    def test_cold_start_pods_contribute_zero(self):
        """A pod younger than coldStartDuration contributes 0 to both
        reclaimable and unreclaimable (peak_predictor.go:154) — via the
        reclaimable mask AND via add_samples' sample mask."""
        import jax.numpy as jnp

        buckets = default_cpu_buckets()
        bank = HistogramBank.zeros(2, buckets, 300.0)
        uids = jnp.asarray([0, 1], jnp.int32)
        values = jnp.asarray([4000.0, 9000.0], jnp.float32)
        # pod 1 is cold-starting: its samples are masked out
        bank = add_samples(bank, buckets, uids, values, jnp.float32(0.0),
                           mask=jnp.asarray([True, False]))
        assert float(bank.total[1]) == 0.0
        reclaim_cpu, _ = pod_reclaimable(
            bank, bank, buckets, buckets,
            pod_request_cpu=jnp.asarray([8000.0, 8000.0]),
            pod_request_mem=jnp.asarray([1024.0, 1024.0]),
            reclaimable_mask=jnp.asarray([True, False]),
            node_allocatable_cpu=jnp.float32(16000.0),
            node_allocatable_mem=jnp.float32(65536.0),
        )
        # only pod 0's (request - peak) survives; the cold pod adds 0
        with_cold, _ = pod_reclaimable(
            bank, bank, buckets, buckets,
            pod_request_cpu=jnp.asarray([8000.0, 0.0]),
            pod_request_mem=jnp.asarray([1024.0, 0.0]),
            reclaimable_mask=jnp.asarray([True, False]),
            node_allocatable_cpu=jnp.float32(16000.0),
            node_allocatable_mem=jnp.float32(65536.0),
        )
        assert float(reclaim_cpu) == float(with_cold)

    def test_empty_bank_sentinel_never_nan(self):
        """An empty histogram answers 0 (the sentinel), and the whole
        predicted-peak tensor stays finite — a NaN here would poison
        the admission reserve and every percent kernel after it."""
        import jax.numpy as jnp

        buckets = default_cpu_buckets()
        bank = HistogramBank.zeros(4, buckets, 300.0)
        p = np.asarray(percentile(bank, buckets, 0.95))
        assert np.all(p == 0.0) and np.all(np.isfinite(p))
        out = np.asarray(kernels.predicted_peaks(
            bank.weights, bank.total, bank.weights, bank.total,
            jnp.float32(120.0), jnp.float32(1.0),
            cpu_buckets=buckets, mem_buckets=buckets))
        assert np.all(out == 0) and out.dtype == np.int32

    def test_percentile_monotone_across_decay_steps(self):
        """p50 <= p95 <= p98 holds at EVERY decay step — including the
        >= 32-half-life renormalization shift — always finite, and a
        fully-decayed bank (every sample below epsilon) falls back to
        the 0 sentinel instead of a NaN or a stale peak."""
        import jax.numpy as jnp

        buckets = default_cpu_buckets()
        bank = HistogramBank.zeros(1, buckets, 10.0)
        rng = np.random.default_rng(7)
        t = 0.0
        for step in range(6):
            values = rng.uniform(100.0, 12_000.0, 8).astype(np.float32)
            bank = add_samples(
                bank, buckets, jnp.zeros(8, jnp.int32),
                jnp.asarray(values), jnp.float32(t))
            p50 = float(percentile(bank, buckets, 0.50)[0])
            p95 = float(percentile(bank, buckets, 0.95)[0])
            p98 = float(percentile(bank, buckets, 0.98)[0])
            assert p50 <= p95 <= p98, (step, p50, p95, p98)
            assert np.isfinite([p50, p95, p98]).all()
            # fresh samples dominate the decayed tail: the p98 answer
            # stays within the current window's value range (a stale
            # undecayed peak would exceed it)
            assert p98 <= float(values.max()) * 1.2
            t += 500.0   # 50 half-lives: every step renormalizes
        # decay-only aging far past every half-life: the whole bank
        # drops below epsilon and the sentinel takes over — never NaN
        bank = add_samples(
            bank, buckets, jnp.zeros(1, jnp.int32),
            jnp.zeros(1, jnp.float32), jnp.float32(t + 10_000.0),
            mask=jnp.asarray([False]))
        aged = float(percentile(bank, buckets, 0.95)[0])
        assert aged == 0.0 and np.isfinite(aged)


# ---------------------------------------------------------------------------
# the plane
# ---------------------------------------------------------------------------


def _fed_plane(capacity=8, hot_row=0, hot_cpu=14_000, valid_rows=4,
               **kw) -> ForecastPlane:
    plane = ForecastPlane(capacity, refresh_interval_s=3600.0, **kw)
    usage = np.zeros((capacity, R), np.int32)
    valid = np.zeros(capacity, bool)
    valid[:valid_rows] = True
    t0 = time.time()
    for t in range(12):
        usage[hot_row, CPU] = hot_cpu
        usage[hot_row, MEM] = 1000
        plane.observe(usage, valid, now=t0 + 30.0 * t)
    plane.refresh(now=t0 + 400.0)
    return plane


class TestForecastPlane:
    def test_observe_refresh_predicts_peak(self):
        plane = _fed_plane()
        assert plane.ready
        peaks = plane.predicted_host()
        # p95 of a constant 14k series, 10% safety margin, one bucket up
        assert 14_000 <= peaks[0, CPU] <= 18_000
        # rows 1-3 observed ZERO usage: their peak is the first bucket
        # bound (~25 mcores with margin), not the hot node's
        assert 0 <= peaks[1, CPU] <= 100
        assert peaks[4, CPU] == 0          # never observed -> sentinel 0
        assert np.all(peaks >= 0)

    def test_error_stats_after_second_refresh(self):
        plane = _fed_plane()
        usage = np.zeros((8, R), np.int32)
        usage[0, CPU] = 14_000
        valid = np.zeros(8, bool)
        valid[:4] = True
        plane.observe(usage, valid, now=time.time() + 500.0)
        plane.refresh(now=time.time() + 600.0)
        # realized 14k vs predicted ~15.4k: a small, finite fraction
        assert 0.0 < plane.error_fraction["cpu"] < 1.0

    def test_horizon_stretches_with_trend_slope(self):
        plane = ForecastPlane(4, base_horizon_s=100.0,
                              max_horizon_scale=4.0, horizon_gain=2.0)
        assert plane.horizon_for(None) == 100.0
        assert plane.horizon_for(-3.0) == 100.0       # falling: base
        assert plane.horizon_for(0.5) == 200.0
        assert plane.horizon_for(50.0) == 400.0       # clamped at 4x

    def test_auto_growth_stretches_horizon_without_external_wiring(self):
        """refresh() with no growth argument derives the trend slope
        from the plane's OWN realized window (trend.fit_slope), so the
        documented horizon stretch works in the production path where
        nothing wires an external signal."""
        plane = ForecastPlane(4, base_horizon_s=100.0,
                              refresh_interval_s=0.0, horizon_gain=1.0)
        usage = np.zeros((4, R), np.int32)
        valid = np.ones(4, bool)
        t0 = time.time()
        level = 1_000
        for window in range(4):
            for t in range(3):
                usage[:, CPU] = level
                plane.observe(usage, valid,
                              now=t0 + window * 60.0 + t * 20.0)
            plane.refresh(now=t0 + window * 60.0 + 40.0)
            level *= 4          # realized mean quadruples per minute
        assert plane.growth_per_hour > 1.0
        assert plane.horizon_s > 100.0

    def test_observe_pads_smaller_snapshots(self):
        """A plane sized AHEAD of its snapshot pads the sample instead
        of crashing the jitted observe (the constructor takes any
        capacity; attach only grows planes, never shrinks them)."""
        plane = ForecastPlane(16, refresh_interval_s=3600.0)
        usage = np.zeros((8, R), np.int32)
        usage[0, CPU] = 5_000
        plane.observe(usage, np.ones(8, bool), now=time.time())
        plane.refresh()
        peaks = plane.predicted_host()
        assert peaks.shape == (16, R)
        assert peaks[0, CPU] > 0 and np.all(peaks[8:] == 0)

    def test_grow_preserves_history(self):
        plane = _fed_plane(capacity=8)
        before = plane.predicted_host()[0, CPU]
        plane.grow(16)
        assert plane.capacity == 16
        plane.refresh(now=time.time() + 500.0)
        assert plane.predicted_host().shape == (16, R)
        assert plane.predicted_host()[0, CPU] >= before * 0.5

    def test_admission_reserve_masks_invalid_and_clamps(self):
        plane = _fed_plane()
        alloc = np.full((8, R), 16_000, np.int32)
        usage = np.zeros((8, R), np.int32)
        usage[0, CPU] = 6_000
        state = ClusterState.from_arrays(alloc[:4], usage=usage[:4],
                                         capacity=8)
        reserve = np.asarray(plane.admission_reserve(state))
        # forecast growth = predicted - observed, never negative
        peaks = plane.predicted_host()
        assert reserve[0, CPU] == max(int(peaks[0, CPU]) - 6_000, 0)
        assert np.all(reserve[4:] == 0)    # invalid rows reserve nothing
        assert np.all(reserve <= MAX_QUANTITY)
        # capacity mismatch -> None (wait for the next observe to grow)
        small = ClusterState.zeros(4)
        assert plane.admission_reserve(small) is None

    def test_sharded_percentile_bit_identical(self):
        """The shard_map percentile twin, pinned like the cluster
        state, answers bit-identically to the single-device kernel at
        mesh width (the per-row math has no cross-shard term)."""
        import jax
        import jax.numpy as jnp

        from koordinator_tpu.parallel import mesh as pmesh

        mesh = pmesh.solver_mesh(jax.devices())
        plane = _fed_plane(capacity=64, valid_rows=64, mesh=mesh)
        ref = np.asarray(plane._peaks_fn(
            plane.cpu_bank.weights, plane.cpu_bank.total,
            plane.mem_bank.weights, plane.mem_bank.total,
            jnp.float32(plane.horizon_s), jnp.float32(0.0)))
        sh = np.asarray(plane._peaks_fn_sh(
            plane.cpu_bank.weights, plane.cpu_bank.total,
            plane.mem_bank.weights, plane.mem_bank.total,
            jnp.float32(plane.horizon_s), jnp.float32(0.0)))
        np.testing.assert_array_equal(ref, sh)


# ---------------------------------------------------------------------------
# the solve entries
# ---------------------------------------------------------------------------


class TestForecastSolveEntries:
    def test_zero_reserve_bit_identical_to_plain_solve(self):
        """forecast_gang_assign with an all-zero reserve IS
        gang_assign: assignments, accounting and quota unchanged."""
        import jax.numpy as jnp

        from koordinator_tpu.ops.assignment import ScoringConfig
        from koordinator_tpu.ops.gang import GangInfo, gang_assign

        from tests.test_mesh import build_problem

        state, pods = build_problem(n_nodes=64, n_pods=16)
        cfg = ScoringConfig.default()
        gangs = GangInfo.build(np.asarray([], np.int32))
        a_ref, st_ref, _ = gang_assign(state, pods, cfg, gangs, None)
        zero = jnp.zeros((64, R), jnp.int32)
        a, st, _ = kernels.forecast_gang_assign(
            state, zero, pods, cfg, gangs, None)
        np.testing.assert_array_equal(np.asarray(a_ref), np.asarray(a))
        np.testing.assert_array_equal(np.asarray(st_ref.node_requested),
                                      np.asarray(st.node_requested))

    def test_reserve_blocks_forecast_hot_nodes(self):
        """A reserve that fills a node's remaining capacity excludes it
        from this round's placements, and the RETURNED state carries no
        trace of the charge (release happened inside the program)."""
        import jax.numpy as jnp

        from koordinator_tpu.ops.assignment import ScoringConfig
        from koordinator_tpu.ops.gang import GangInfo, gang_assign

        from tests.test_mesh import build_problem

        state, pods = build_problem(n_nodes=8, n_pods=4)
        cfg = ScoringConfig.default()
        gangs = GangInfo.build(np.asarray([], np.int32))
        free = np.asarray(state.free)
        reserve = np.zeros((8, R), np.int32)
        reserve[0] = free[0]                   # node 0 forecast-full
        a, st, _ = kernels.forecast_gang_assign(
            state, jnp.asarray(reserve), pods, cfg, gangs, None)
        a = np.asarray(a)
        assert not np.any(a[: 4] == 0), "forecast-full node 0 was used"
        # release proof: requested == original + placed requests only
        a_ref, st_ref, _ = gang_assign(state, pods, cfg, gangs, None)
        placed = np.asarray(pods.requests)[:4][a[:4] >= 0]
        expect = np.asarray(state.node_requested).copy()
        for row, req in zip(a[:4][a[:4] >= 0], placed):
            expect[row] += req
        np.testing.assert_array_equal(np.asarray(st.node_requested),
                                      expect)

    def test_sharded_forecast_entry_bit_identical_on_2d_mesh(self):
        """The sharded twin matches the single-device forecast entry on
        a 2-D (pods x nodes) mesh — the acceptance bar's parity clause
        for forecast rounds."""
        import jax
        import jax.numpy as jnp

        from koordinator_tpu.ops.assignment import ScoringConfig
        from koordinator_tpu.ops.gang import GangInfo
        from koordinator_tpu.parallel import mesh as pmesh
        from koordinator_tpu.parallel import sharded as ps

        from tests.test_mesh import build_problem

        state, pods = build_problem(n_nodes=64, n_pods=32)
        cfg = ScoringConfig.default()
        gangs = GangInfo.build(np.asarray([], np.int32))
        rng = np.random.default_rng(5)
        reserve = np.zeros((64, R), np.int32)
        reserve[:, CPU] = rng.integers(0, 8_000, 64)
        reserve = jnp.asarray(reserve)
        a_ref, st_ref, _ = kernels.forecast_gang_assign(
            state, reserve, pods, cfg, gangs, None, solver="batch")
        mesh = pmesh.solver_mesh(jax.devices(), pods_axis=2)
        a_sh, st_sh, _, _ = ps.sharded_forecast_gang_assign(
            mesh, state, reserve, pods, cfg, gangs, None, solver="batch")
        np.testing.assert_array_equal(np.asarray(a_ref), np.asarray(a_sh))
        np.testing.assert_array_equal(np.asarray(st_ref.node_requested),
                                      np.asarray(st_sh.node_requested))


# ---------------------------------------------------------------------------
# scheduler integration
# ---------------------------------------------------------------------------


def _scheduler(mode="off", quota=False):
    from koordinator_tpu.quota.tree import UNBOUNDED, QuotaTree
    from koordinator_tpu.scheduler import ClusterSnapshot, Scheduler
    from koordinator_tpu.scheduler.snapshot import NodeSpec

    tree = None
    if quota:
        total = np.zeros(R, np.int64)
        total[CPU] = 64_000
        tree = QuotaTree(total)
        mx = np.full(R, UNBOUNDED, np.int64)
        mx[CPU] = 20_000
        tree.add("q", min=np.zeros(R, np.int64), max=mx)
    snap = ClusterSnapshot(capacity=8)
    for i in range(4):
        snap.upsert_node(NodeSpec(
            name=f"n{i}",
            allocatable=resource_vector(cpu=16_000, memory=65_536)))
    return Scheduler(snap, forecast_mode=mode,
                     solver_kit=SolverKit(mesh=None), quota_tree=tree)


def _enqueue(s, n=6, cpu=4_000):
    from koordinator_tpu.scheduler.snapshot import PodSpec

    for j in range(n):
        s.enqueue(PodSpec(name=f"p{j}",
                          requests=resource_vector(cpu=cpu, memory=8_192),
                          priority=10, quota="q" if s.quota_tree else None))


class TestSchedulerForecastMode:
    def test_modes(self):
        assert FORECAST_MODES == ("off", "admit", "full")
        with pytest.raises(ValueError, match="unknown forecast_mode"):
            _scheduler(mode="bogus")

    def test_off_and_inert_and_zero_reserve_identical(self):
        """Acceptance: forecast_mode=off is bit-identical — and so are
        an admit scheduler with no plane, and an admit scheduler whose
        plane predicts nothing (the zero reserve charges through the
        forecast ENTRY and still changes no decision or quota charge).
        """
        outcomes = {}
        for tag in ("off", "admit-noplane", "admit-zeroplane"):
            s = _scheduler(mode=("off" if tag == "off" else "admit"),
                           quota=True)
            if tag == "admit-zeroplane":
                plane = ForecastPlane(8, refresh_interval_s=3600.0)
                plane.observe(np.zeros((8, R), np.int32),
                              np.ones(8, bool))
                plane.refresh()
                s.attach_forecast_plane(plane)
            _enqueue(s)
            r = s.schedule_round()
            outcomes[tag] = (
                dict(sorted(r.assignments.items())),
                sorted(r.failures),
                np.asarray(s.quota_tree.nodes["q"].used).tolist(),
            )
        assert outcomes["off"] == outcomes["admit-noplane"]
        assert outcomes["off"] == outcomes["admit-zeroplane"]

    def test_admission_steers_off_forecast_hot_node(self):
        from koordinator_tpu import metrics

        s = _scheduler(mode="admit")
        plane = _fed_plane()
        s.attach_forecast_plane(plane)
        _enqueue(s)
        r = s.schedule_round()
        assert "n0" not in r.assignments.values()
        assert len(r.assignments) == 6     # capacity elsewhere suffices
        assert metrics.forecast_admission_reserved_fraction.value() > 0

    def test_plane_survives_the_donating_solve(self):
        """The plane must never retain the snapshot's own buffers: the
        round's solve DONATES the state the prelude observed, and a
        held reference would leave refresh()/report() reading a
        deleted array (the e2e gateway drive caught exactly this)."""
        s = _scheduler(mode="admit")
        plane = _fed_plane()
        s.attach_forecast_plane(plane)
        _enqueue(s)
        s.schedule_round()          # prelude observes, solve donates
        plane.refresh()             # reads _valid: must be a live copy
        body = plane.report(max_nodes=4)
        assert body["ready"] and body["nodes"]

    def test_full_queue_fails_with_capacity_reason_when_reserved(self):
        """When the reserve makes demand exceed remaining capacity the
        overflow pods fail with a real capacity diagnosis, not a
        crash."""
        s = _scheduler(mode="admit")
        s.attach_forecast_plane(_fed_plane())
        _enqueue(s, n=14, cpu=4_000)   # 56k asks vs 3x16k unreserved
        r = s.schedule_round()
        assert r.failures and "n0" not in r.assignments.values()

    def test_debug_forecast_surface(self):
        from koordinator_tpu.scheduler.services import DebugService

        s = _scheduler(mode="admit")
        svc = DebugService(s)
        status, body = svc.handle("/debug/forecast")
        assert status == 501 and "forecast" in body["error"]
        s.attach_forecast_plane(_fed_plane())
        status, body = svc.handle("/debug/forecast", {"nodes": "2"})
        assert status == 200
        assert body["mode"] == "admit" and body["ready"]
        assert len(body["nodes"]) <= 2
        assert body["nodes"][0]["node"] == "n0"     # hottest first
        assert "admission_reserved_fraction" in body
        status, body = svc.handle("/debug/forecast", {"nodes": "x"})
        assert status == 400

    def test_tenant_labels_stamp_the_plane(self):
        """attach stamps the scheduler's tenant onto the plane's gauge
        labels — per-tenant planes must not overwrite each other's
        forecast telemetry."""
        from koordinator_tpu import metrics
        from koordinator_tpu.scheduler import ClusterSnapshot, Scheduler

        s = Scheduler(ClusterSnapshot(capacity=8), forecast_mode="admit",
                      solver_kit=SolverKit(mesh=None), tenant="t7")
        plane = _fed_plane()
        s.attach_forecast_plane(plane)
        assert plane.metric_labels == {"tenant": "t7"}
        plane.refresh()
        assert metrics.forecast_horizon_seconds.value(
            labels={"tenant": "t7"}) > 0


# ---------------------------------------------------------------------------
# predictive colocation
# ---------------------------------------------------------------------------


class TestPredictiveColocation:
    def test_batch_allocatable_shrinks_before_the_ramp(self):
        """With the forecast seam attached, the colocation loop's very
        next node_allocatable push advertises batch capacity computed
        from the PREDICTED peak — before observed usage moves at all;
        without it the push is byte-identical to the reactive loop."""
        from koordinator_tpu.forecast.colocation import PredictiveColocation
        from koordinator_tpu.manager.colocation_loop import (
            ColocationLoop,
            ManagerSyncBinding,
        )
        from koordinator_tpu.manager.noderesource_controller import (
            NodeResourceController,
        )
        from koordinator_tpu.transport import StateSyncService

        clock = lambda: 1000.0  # noqa: E731

        def build(forecast):
            service = StateSyncService()
            binding = ManagerSyncBinding(clock=clock)
            service.attach_binding(binding)
            service.upsert_node("n0",
                                resource_vector(cpu=16_000, memory=16_384))
            service.update_node_usage(
                "n0", resource_vector(cpu=2_000, memory=2_048),
                hp_usage=resource_vector(cpu=2_000, memory=2_048))
            pushes = []
            loop = ColocationLoop(
                NodeResourceController(clock=clock), binding,
                lambda names, alloc: pushes.extend(np.array(alloc)),
                forecast=forecast)
            loop.tick()
            return pushes

        plane = _fed_plane(hot_cpu=12_000)   # predicted ~13.2k vs 2k seen
        rows = {"n0": 0}
        predictive = build(PredictiveColocation(plane, rows.get))
        reactive = build(None)
        assert len(predictive) == 1 and len(reactive) == 1
        batch_cpu = ResourceDim.BATCH_CPU
        # reactive: cap - 40% margin - 2k observed = 7.6k; predictive
        # subtracts the ~13.2k predicted peak instead
        assert reactive[0][batch_cpu] > 7_000
        assert predictive[0][batch_cpu] < reactive[0][batch_cpu] - 5_000
        # prod dims ride through untouched in both
        assert predictive[0][CPU] == reactive[0][CPU] == 16_000


# ---------------------------------------------------------------------------
# proactive rebalance
# ---------------------------------------------------------------------------


def _rebalance_fixture(hot_cpu=14_000, under_rows=True):
    import jax.numpy as jnp

    from koordinator_tpu.descheduler.lownodeload import LowNodeLoadArgs
    from koordinator_tpu.descheduler.migration import (
        ArbitrationLimits,
        MigrationController,
    )
    from koordinator_tpu.forecast.rebalance import ProactiveRebalancer

    plane = _fed_plane(hot_cpu=hot_cpu)
    pods = ["be-0", "be-1"]
    universe = (
        pods,
        np.asarray([0, 0], np.int32),
        np.asarray([[0] * R] * 2, np.int32),
        np.zeros(2, np.int32),
        np.ones(2, bool),
    )
    universe[2][:, CPU] = 1_000
    reserved, evicted = [], []
    controller = MigrationController(
        limits=ArbitrationLimits(max_migrating_per_node=4),
        reserve_many=lambda jobs: {
            job.name: reserved.append(job.pod) or f"rsv-{job.pod}"
            for job in jobs},
        evict_fn=lambda job: evicted.append(job.pod) or True)
    args = LowNodeLoadArgs.default()
    args = args.replace(anomaly_rounds=jnp.int32(2))
    reb = ProactiveRebalancer(
        plane, controller, pods_fn=lambda: universe,
        node_name_fn=lambda row: f"n{row}", args=args)
    usage = np.zeros((8, R), np.int32)
    usage[0, CPU] = 2_000 + 6_000   # observed: calm — forecast: hot
    if not under_rows:
        usage[:4, CPU] = 12_000     # nowhere to move anything
    capacity = np.zeros((8, R), np.int32)
    capacity[:4, CPU] = 16_000
    capacity[:4, MEM] = 65_536
    valid = np.zeros(8, bool)
    valid[:4] = True
    return reb, controller, usage, capacity, valid, reserved, evicted


class TestProactiveRebalance:
    def test_prestages_reservation_first_moves(self):
        from koordinator_tpu import metrics
        from koordinator_tpu.descheduler.migration import MigrationJobPhase

        reb, controller, usage, capacity, valid, reserved, evicted = (
            _rebalance_fixture())
        assert reb.tick(usage, capacity, valid) == []   # anomaly round 1
        moves = reb.tick(usage, capacity, valid)        # round 2: stage
        assert moves and all(m.node == "n0" for m in moves)
        assert all(m.dest != "n0" for m in moves)
        assert sum(v for _, v in
                   metrics.forecast_evictions_prestaged.items()) == len(
                       moves)
        controller.reconcile()
        # reservation-first: capacity reserved BEFORE the eviction ran
        assert reserved and evicted
        for move in moves:
            assert move.job.phase is MigrationJobPhase.SUCCEEDED
            assert move.job.reservation == f"rsv-{move.pod}"
        # a released pod may stage again; an unreleased one must not
        reb.release(moves[0].pod)
        assert moves[0].pod not in reb._staged

    def test_cost_gate_blocks_without_destinations(self):
        reb, controller, usage, capacity, valid, reserved, _ = (
            _rebalance_fixture(under_rows=False))
        reb.tick(usage, capacity, valid)
        moves = reb.tick(usage, capacity, valid)
        assert moves == [] and not reserved

    def test_migration_cost_gate_sequential_feedback(self):
        """Two pods cannot both claim the last slot: the second
        candidate sees the first's charge."""
        import jax.numpy as jnp

        usage = np.zeros((2, R), np.int32)
        usage[0, CPU] = 9_000          # under node with ~1.4k of room
        capacity = np.full((2, R), 16_000, np.int32)
        high = np.full(R, -1, np.int32)
        high[CPU] = 65                 # high_quant = 10_400
        pods = np.zeros((2, R), np.int32)
        pods[:, CPU] = 1_000
        under = np.asarray([True, False])
        gate, dest = kernels.migration_cost_gate(
            jnp.asarray(pods), jnp.asarray(usage), jnp.asarray(capacity),
            jnp.asarray(under), jnp.asarray(high))
        gate, dest = np.asarray(gate), np.asarray(dest)
        assert gate[0] and dest[0] == 0
        assert not gate[1] and dest[1] == -1


# ---------------------------------------------------------------------------
# the A/B proof
# ---------------------------------------------------------------------------


AB_SMOKE = dict(seed=0, nodes=8, periods=2, period_s=360.0, tick_s=24.0,
                half_life_s=180.0, refresh_interval_s=24.0)


class TestForecastAB:
    def test_trace_deterministic(self):
        from koordinator_tpu.forecast.ab import ABConfig, generate_ls_trace

        cfg = ABConfig(**AB_SMOKE)
        t1, t2 = generate_ls_trace(cfg), generate_ls_trace(cfg)
        np.testing.assert_array_equal(t1, t2)
        # flat half really is flat, spiky half really swings
        spread = t1.max(axis=0) - t1.min(axis=0)
        assert spread[:4].max() < spread[4:].min()

    def test_predictive_arm_wins_the_ab(self):
        """The acceptance clause: under one seeded diurnal trace the
        predictive arm shows fewer SLO-breach minutes AND fewer
        reactive evictions, with the proactive path exercised."""
        from koordinator_tpu.forecast.ab import ABConfig, run_ab

        doc = run_ab(ABConfig(**AB_SMOKE))
        r, p = doc["reactive"], doc["predictive"]
        assert doc["predictive_no_worse"]
        assert doc["predictive_strictly_better"], (r, p)
        assert p["prestaged_migrations"] > 0
        assert p["migrations_completed"] > 0
        assert 0.0 < p["forecast_error_fraction"]["cpu"] < 1.0
        # the win is not "BE never ran": the predictive arm keeps a
        # substantial share of the reactive arm's BE occupancy
        assert p["be_pod_ticks"] > r["be_pod_ticks"] * 0.5
