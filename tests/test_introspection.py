"""JAX solver introspection (ISSUE 5): recompiles, device bytes, profiler.

The acceptance recompile test: changing a batch shape bucket increments
``solver_recompiles_total`` exactly as expected — and same-shape rounds
increment nothing; the device-bytes gauge matches ``nbytes`` of the live
``ClusterState``/``CandidateCache`` arrays.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from koordinator_tpu import metrics
from koordinator_tpu.api.resources import resource_vector
from koordinator_tpu.ops import introspection as insp
from koordinator_tpu.scheduler import ClusterSnapshot, Scheduler
from koordinator_tpu.scheduler.snapshot import NodeSpec, PodSpec
from koordinator_tpu.scheduler.solver_kit import SolverKit


def recompile_totals() -> dict:
    """{(fn, shape): count} snapshot of solver_recompiles_total."""
    return {(labels["fn"], labels["shape"]): value
            for labels, value in metrics.solver_recompiles.items()}


class TestInstrumentedJit:
    def test_counts_misses_per_shape_bucket(self):
        fn = insp.instrument(
            jax.jit(lambda x: x + 1), "plus_one",
            shape_of=lambda a, k: f"N{a[0].shape[0]}")
        before = recompile_totals()

        out = fn(jnp.zeros(4))
        assert out.shape == (4,)
        assert metrics.solver_recompiles.value(
            {"fn": "plus_one", "shape": "N4"}) == before.get(
                ("plus_one", "N4"), 0) + 1
        fn(jnp.ones(4))    # warm: same shape, no miss
        assert metrics.solver_recompiles.value(
            {"fn": "plus_one", "shape": "N4"}) == before.get(
                ("plus_one", "N4"), 0) + 1
        fn(jnp.zeros(8))   # new shape bucket: one miss
        assert metrics.solver_recompiles.value(
            {"fn": "plus_one", "shape": "N8"}) == 1
        assert fn.misses == 2
        assert metrics.solver_jit_cache_size.value(
            {"fn": "plus_one"}) == 2.0

    def test_default_shape_label_and_shape_of_failure(self):
        label = insp.default_shape_of((jnp.zeros((4, 2)), jnp.zeros(3)), {})
        assert "4x2" in label and "3" in label

        def broken_shape_of(a, k):
            raise RuntimeError("labeling bug")

        fn = insp.instrument(jax.jit(lambda x: x * 2), "twice",
                             shape_of=broken_shape_of)
        fn(jnp.zeros(2))   # the solve must survive a labeling bug
        assert metrics.solver_recompiles.value(
            {"fn": "twice", "shape": "unknown"}) == 1

    def test_uninstrumentable_fn_is_refused(self):
        """No silent pass-through: a wrapper that cannot see the jit
        cache would report zero recompiles forever."""
        with pytest.raises(TypeError, match="_cache_size"):
            insp.instrument(lambda x: x + 1, "plain")

    def test_device_bytes_sums_leaf_nbytes(self):
        from koordinator_tpu.state.cluster_state import ClusterState

        state = ClusterState.zeros(16)
        expect = sum(int(leaf.nbytes) for leaf in jax.tree.leaves(state))
        assert insp.device_bytes(state) == expect
        assert insp.device_bytes(None) == 0


class TestSchedulerRecompileAccounting:
    """The acceptance test: shape-bucket changes produce exactly the
    expected increments; same-shape rounds produce zero."""

    def make_sched(self, **kw):
        snap = ClusterSnapshot(capacity=64)
        snap.upsert_node(NodeSpec(
            name="n0",
            allocatable=resource_vector(cpu=10_000_000,
                                        memory=10_000_000)))
        return Scheduler(snap, batch_solver_threshold=1, **kw)

    def enqueue_n(self, sched, n, prefix):
        for i in range(n):
            sched.enqueue(PodSpec(
                name=f"{prefix}{i}",
                requests=resource_vector(cpu=100, memory=64)))

    def test_full_path_exact_increments_on_shape_change(self):
        sched = self.make_sched(incremental_solve=False)
        # 20 pods -> pod bucket 32 (power-of-two, min 16)
        self.enqueue_n(sched, 20, "a")
        sched.schedule_round()
        after_cold = recompile_totals()
        assert after_cold[("gang_assign", "P32xN64")] == 1

        # same shape bucket again: ZERO increments anywhere
        self.enqueue_n(sched, 20, "b")
        sched.schedule_round()
        assert recompile_totals() == after_cold

        # 40 pods -> bucket 64: exactly ONE increment, on gang_assign's
        # new shape label (the only jitted entry the full path runs)
        self.enqueue_n(sched, 40, "c")
        sched.schedule_round()
        after_grow = recompile_totals()
        delta = {k: v - after_cold.get(k, 0) for k, v in after_grow.items()
                 if v != after_cold.get(k, 0)}
        assert delta == {("gang_assign", "P64xN64"): 1}

    def test_incremental_path_warm_rounds_add_zero(self):
        sched = self.make_sched()
        # round 1 compiles the cold path (select + pass1); round 2 is
        # the first with a live candidate cache, compiling the align
        # kernel — the steady-state working set is warm after it
        self.enqueue_n(sched, 20, "a")
        sched.schedule_round()
        assert any(fn == "assign_round_pass" and shape.startswith("P32")
                   for fn, shape in recompile_totals())
        self.enqueue_n(sched, 20, "b")
        sched.schedule_round()
        warm = recompile_totals()
        # same-shape steady state: the whole pipeline re-runs with
        # ZERO further misses across rounds
        for batch in ("c", "d"):
            self.enqueue_n(sched, 20, batch)
            sched.schedule_round()
        assert recompile_totals() == warm

    def test_device_bytes_gauge_matches_live_arrays(self):
        sched = self.make_sched()
        self.enqueue_n(sched, 20, "a")
        sched.schedule_round()
        assert metrics.solver_device_bytes.value(
            {"kind": "cluster_state"}) == float(
                insp.device_bytes(sched.snapshot.state))
        cand = sched._cand_cache
        assert cand is not None
        assert metrics.solver_device_bytes.value(
            {"kind": "candidate_cache"}) == float(
                insp.device_bytes(cand["cache"]))
        assert metrics.solver_device_bytes.value(
            {"kind": "candidate_cache"}) > 0

    def test_padding_waste_fraction(self):
        sched = self.make_sched()
        self.enqueue_n(sched, 20, "a")   # bucket 32 -> 12/32 wasted
        sched.schedule_round()
        assert metrics.solver_batch_padding_waste.value() == pytest.approx(
            1.0 - 20 / 32)


class TestProfilerCapture:
    def test_gate_off_by_default(self):
        cap = insp.ProfilerCapture()
        with pytest.raises(insp.ProfileDisabled):
            cap.capture(0.01)

    def test_capture_with_stub_profiler(self, tmp_path):
        calls = []

        class StubProfiler:
            def start_trace(self, out_dir):
                calls.append(("start", out_dir))

            def stop_trace(self):
                calls.append(("stop", None))

        cap = insp.ProfilerCapture(
            enabled=True, out_dir=str(tmp_path), max_seconds=5.0,
            profiler=StubProfiler(), sleep=lambda s: calls.append(
                ("sleep", s)))
        out = cap.capture(2.0)
        assert out == {"dir": str(tmp_path), "seconds": 2.0}
        assert [c[0] for c in calls] == ["start", "sleep", "stop"]
        assert cap.captures == 1

    def test_seconds_clamped_to_max(self, tmp_path):
        class StubProfiler:
            def start_trace(self, out_dir):
                pass

            def stop_trace(self):
                pass

        slept = []
        cap = insp.ProfilerCapture(
            enabled=True, out_dir=str(tmp_path), max_seconds=0.5,
            profiler=StubProfiler(), sleep=slept.append)
        assert cap.capture(600.0)["seconds"] == 0.5
        assert slept == [0.5]

    def test_stop_trace_runs_even_when_sleep_dies(self, tmp_path):
        calls = []

        class StubProfiler:
            def start_trace(self, out_dir):
                calls.append("start")

            def stop_trace(self):
                calls.append("stop")

        def bad_sleep(s):
            raise KeyboardInterrupt

        cap = insp.ProfilerCapture(
            enabled=True, out_dir=str(tmp_path),
            profiler=StubProfiler(), sleep=bad_sleep)
        with pytest.raises(KeyboardInterrupt):
            cap.capture(0.1)
        assert calls == ["start", "stop"]
        # the lock released: a next capture is not spuriously busy
        cap._sleep = lambda s: None
        assert cap.capture(0.1)["seconds"] == 0.1

    def test_debug_profile_routes_when_enabled(self):
        from koordinator_tpu.scheduler.services import DebugService

        class StubProfiler:
            def start_trace(self, out_dir):
                pass

            def stop_trace(self):
                pass

        snap = ClusterSnapshot(capacity=8)
        snap.upsert_node(NodeSpec(
            name="n0", allocatable=resource_vector(cpu=1000, memory=1000)))
        sched = Scheduler(snap)
        service = DebugService(sched)
        # gate off (the default): 403
        status, body = service.handle("/debug/profile", {"seconds": 0.01})
        assert status == 403
        # armed: the capture runs and returns its artifact dir
        sched.profile_capture = insp.ProfilerCapture(
            enabled=True, out_dir="/tmp/x", profiler=StubProfiler(),
            sleep=lambda s: None)
        status, body = service.handle("/debug/profile", {"seconds": 0.25})
        assert status == 200
        assert body == {"dir": "/tmp/x", "seconds": 0.25}
        status, body = service.handle("/debug/profile",
                                      {"seconds": "nope"})
        assert status == 400
        # nan parses as a float but must not start a trace (it would
        # die inside sleep() as a blanket 500)
        status, body = service.handle("/debug/profile",
                                      {"seconds": "nan"})
        assert status == 400

class TestShardedIntrospection:
    """ISSUE 10 satellite: per-shard device bytes, collective counts,
    the solver_shard_count gauge, and the /debug/slo sharding section."""

    def test_device_bytes_by_shard_single_device(self):
        a = jnp.zeros((16, 4), jnp.int32)
        by = insp.device_bytes_by_shard(a)
        assert sum(by.values()) == a.nbytes and len(by) == 1
        assert insp.device_bytes_by_shard(None) == {}

    def test_device_bytes_by_shard_sharded_and_replicated(self):
        from koordinator_tpu.parallel import mesh as pmesh

        mesh = pmesh.solver_mesh()
        sharded = jax.device_put(jnp.zeros((64, 4), jnp.int32),
                                 pmesh.node_sharding(mesh))
        by = insp.device_bytes_by_shard(sharded)
        # node-sharded: the slices sum to the global footprint, spread
        # over every device of the mesh
        assert sum(by.values()) == sharded.nbytes
        assert len(by) == len(jax.devices())
        rep = jax.device_put(
            jnp.zeros((8,), jnp.int32),
            jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()))
        by_rep = insp.device_bytes_by_shard(rep)
        # replicated: every device honestly pays a full copy
        assert all(v == rep.nbytes for v in by_rep.values())

    def test_collective_counts_parses_hlo(self):
        txt = """
  %ag = s32[4,8]{1,0} all-gather(s32[4,1]{1,0} %x), replica_groups={}
  %ar.1 = s32[4]{0} all-reduce(s32[4]{0} %y), to_apply=%sum
  %ars = s32[2]{0} reduce-scatter(s32[4]{0} %z), to_apply=%sum
  %not_a_match = s32[] add(s32[] %a, s32[] %b)
"""
        got = insp.collective_counts(txt)
        assert got == {"all-gather": 1, "all-reduce": 1,
                       "reduce-scatter": 1}

    def test_compiled_collectives_counts_sharded_psum(self):
        from koordinator_tpu.parallel import mesh as pmesh
        from koordinator_tpu.parallel import sharded as ps

        mesh = pmesh.solver_mesh()
        from functools import partial

        from jax.sharding import PartitionSpec as P

        fn = jax.jit(jax.shard_map(
            lambda x: jax.lax.psum(x.sum(), ps.NODES_AXIS),
            mesh=mesh, in_specs=(P("nodes"),), out_specs=P(),
            check_vma=False))
        got = insp.compiled_collectives(fn, jnp.zeros((64,), jnp.int32))
        assert got.get("all-reduce", 0) >= 1, got

    def test_sharding_report_and_debug_slo_section(self):
        from types import SimpleNamespace

        from koordinator_tpu.scheduler.services import debug_slo_body

        snap = ClusterSnapshot(capacity=64)
        sched = Scheduler(snap, solver_kit=SolverKit(shard_min_nodes=0))
        assert sched.kit.shards == len(jax.devices())
        report = sched.sharding_report()
        assert report["active"] and report["mesh"]["nodes"] == 8
        assert "cluster_state" in report["device_bytes_by_shard"]
        assert len(report["device_bytes_by_shard"]["cluster_state"]) == 8
        sched.slo_monitor = SimpleNamespace(report=lambda: {"slos": []})
        body = debug_slo_body(sched)
        assert body["sharding"]["solver_shard_count"] == 8
        # mesh off => the report says so and the gauge path reads 1
        single = Scheduler(ClusterSnapshot(capacity=64),
                           solver_kit=SolverKit(mesh="off"))
        rep = single.sharding_report()
        assert rep["solver_shard_count"] == 1 and rep["mesh"] is None

    def test_solver_shard_count_gauge_set_per_round(self):
        snap = ClusterSnapshot(capacity=64)
        snap.upsert_node(NodeSpec(
            name="n0", allocatable=resource_vector(cpu=10_000,
                                                   memory=10_000)))
        sched = Scheduler(snap, batch_solver_threshold=1,
                          solver_kit=SolverKit(shard_min_nodes=0))
        sched.enqueue(PodSpec(
            name="p0", requests=resource_vector(cpu=100, memory=64)))
        sched.schedule_round()
        assert metrics.solver_shard_count.value() == float(
            len(jax.devices()))
        # per-shard byte rows carry the shard label
        assert any("shard" in labels
                   for labels, _ in metrics.solver_device_bytes.items())
