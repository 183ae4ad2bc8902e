"""Steady-state observatory smoke (ISSUE 9, tier-1): deterministic
churn-trace generation, the socket-driven harness completing a seeded
soak with a GREEN verdict, the same harness CATCHING planted
thread/queue leaks, /debug/steady parity across both surfaces, and the
flight-ring-size satellite.

Fast + deterministic by construction: small scale, fixed seeds,
time-compressed replay; heavy imports (the scheduler stack) stay
inside test functions per the marker-audit convention.
"""

import os
import sys
import threading

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))

import loadgen  # noqa: E402  (tools/loadgen.py; no JAX at module scope)


class TestTraceGeneration:
    def test_same_seed_same_trace(self):
        cfg = loadgen.smoke_config(seed=13)
        a = [e.to_doc() for e in loadgen.generate_trace(cfg)]
        b = [e.to_doc() for e in loadgen.generate_trace(cfg)]
        assert a == b

    def test_different_seeds_differ(self):
        a = loadgen.generate_trace(loadgen.smoke_config(seed=1))
        b = loadgen.generate_trace(loadgen.smoke_config(seed=2))
        assert [e.to_doc() for e in a] != [e.to_doc() for e in b]

    def test_trace_is_sorted_and_covers_every_kind(self):
        events = loadgen.generate_trace(loadgen.smoke_config(seed=7))
        ts = [e.t for e in events]
        assert ts == sorted(ts)
        kinds = {e.kind for e in events}
        assert kinds == set(loadgen.EVENT_KINDS)

    def test_deletes_follow_adds_and_stay_inside_duration(self):
        cfg = loadgen.smoke_config(seed=3)
        events = loadgen.generate_trace(cfg)
        added_at = {e.name: e.t for e in events if e.kind == loadgen.POD_ADD}
        for e in events:
            if e.kind == loadgen.POD_DEL:
                assert e.name in added_at
                assert added_at[e.name] <= e.t <= cfg.duration_s

    def test_node_flaps_pair_down_then_up(self):
        cfg = loadgen.smoke_config(seed=5)
        events = loadgen.generate_trace(cfg)
        down: dict[str, float] = {}
        for e in events:
            if e.kind == loadgen.NODE_DOWN:
                assert e.name not in down   # no double-down
                down[e.name] = e.t
            elif e.kind == loadgen.NODE_UP:
                assert down.pop(e.name) < e.t

    def test_jsonl_roundtrip(self, tmp_path):
        events = loadgen.generate_trace(loadgen.smoke_config(seed=11))
        path = str(tmp_path / "trace.jsonl")
        loadgen.write_trace(events, path)
        back = loadgen.read_trace(path)
        assert [e.to_doc() for e in back] == [e.to_doc() for e in events]

    def test_diurnal_rate_modulates_arrivals(self):
        import dataclasses

        cfg = dataclasses.replace(
            loadgen.LoadGenConfig(seed=4), duration_s=600.0, nodes=4,
            arrival_rate=4.0, diurnal_amplitude=0.9,
            diurnal_period_s=600.0, gang_rate=0.0, node_flap_rate=0.0,
            quota_churn_rate=0.0, pod_lifetime_s=1e9)
        adds = [e.t for e in loadgen.generate_trace(cfg)
                if e.kind == loadgen.POD_ADD]
        # first half rides the sine peak, second half the trough
        first = sum(1 for t in adds if t < 300.0)
        second = len(adds) - first
        assert first > second * 1.5

    def test_stats_shape(self):
        events = loadgen.generate_trace(loadgen.smoke_config(seed=0))
        stats = loadgen.trace_stats(events)
        assert stats["events"] == len(events)
        assert stats["arrival_rate"] > 0


class TestTenantTraces:
    """--tenants N (ISSUE 11): tenant-tagged, per-tenant-seeded,
    deterministic multi-cluster traces."""

    def test_every_event_carries_its_tenant(self):
        cfg = loadgen.smoke_config(seed=4, tenants=3)
        events = loadgen.generate_trace(cfg)
        tenants = {e.payload.get("tenant") for e in events}
        assert tenants == {"t0", "t1", "t2"}

    def test_same_seed_same_multi_tenant_trace(self):
        cfg = loadgen.smoke_config(seed=13, tenants=4)
        a = [e.to_doc() for e in loadgen.generate_trace(cfg)]
        b = [e.to_doc() for e in loadgen.generate_trace(cfg)]
        assert a == b

    def test_tenant_subtrace_is_the_derived_seed_trace(self):
        """Tenant t's sub-stream must be byte-identical to a
        single-tenant trace generated directly from tenant_seed(seed,
        t) — the per-tenant-seed determinism contract."""
        import dataclasses

        cfg = loadgen.smoke_config(seed=6, tenants=3)
        merged = loadgen.generate_trace(cfg)
        for i, name in enumerate(cfg.tenant_names()):
            sub = [
                {k: v for k, v in e.to_doc().items() if k != "tenant"}
                for e in merged if e.payload.get("tenant") == name]
            direct = loadgen.generate_trace(dataclasses.replace(
                cfg, seed=loadgen.tenant_seed(cfg.seed, i), tenants=1))
            assert sub == [e.to_doc() for e in direct]

    def test_tenants_differ_from_each_other(self):
        cfg = loadgen.smoke_config(seed=8, tenants=2)
        events = loadgen.generate_trace(cfg)
        t0 = [e.to_doc() for e in events
              if e.payload.get("tenant") == "t0"]
        t1 = [e.to_doc() for e in events
              if e.payload.get("tenant") == "t1"]
        assert t0 and t1
        assert t0 != t1

    def test_stats_tally_per_tenant(self):
        cfg = loadgen.smoke_config(seed=2, tenants=2)
        stats = loadgen.trace_stats(loadgen.generate_trace(cfg))
        assert set(stats["tenants"]) == {"t0", "t1"}
        assert sum(stats["tenants"].values()) == stats["events"]

    def test_jsonl_roundtrip_keeps_tenant_field(self, tmp_path):
        cfg = loadgen.smoke_config(seed=3, tenants=2)
        events = loadgen.generate_trace(cfg)
        path = str(tmp_path / "mt.jsonl")
        loadgen.write_trace(events, path)
        back = loadgen.read_trace(path)
        assert [e.to_doc() for e in back] == [e.to_doc() for e in events]


class TestMultiTenantSoak:
    """The harness replays one trace stream per tenant against a
    TenantScheduler (one socket stack + sync binding per tenant) and
    the verdict grows a populated per-tenant section."""

    def test_multi_tenant_soak_green_with_per_tenant_section(
            self, tmp_path):
        import dataclasses

        cfg = dataclasses.replace(
            loadgen.smoke_config(seed=7, tenants=3), duration_s=50.0,
            nodes=12)
        events = loadgen.generate_trace(cfg)
        harness = loadgen.SteadyStateHarness(
            cfg, str(tmp_path), time_scale=15.0, solve_interval_s=4.0,
            slo_latency_threshold_s=5.0)
        harness.start()
        try:
            verdict = harness.run(events)
        finally:
            harness.close()
        # Held to what the harness COUNTED.  ``green`` also folds in two
        # readings of the host's clock and allocator: the slope of the
        # process's RSS and the p99 of round latency over a 50 s trace
        # compressed into 3.3 s of wall, jit compiles included.  Those
        # say how busy the host was (beside five other test workers:
        # red), not what the front-end did; TestGreenSoak holds the
        # harness to them over a longer window.
        trend = verdict["trend"]
        assert verdict["green"] == (
            not trend["leaking"] and not trend["drifting"]
            and not verdict["slo_breached"] and not verdict["degraded"])
        counted_red = [name for name in trend["leaking"] + trend["drifting"]
                       if not name.startswith("koord_process_rss_bytes")]
        assert not counted_red      # threads, fds, queue depth, backlog
        assert not verdict["degraded"]
        tenants = verdict["tenants"]
        assert set(tenants) == {"t0", "t1", "t2"}
        # every tenant's cluster actually flowed: rounds ran, pods bound
        for name, doc in tenants.items():
            assert doc["rounds"] > 0, (name, doc)
            assert doc["bound"] > 0, (name, doc)
            assert not doc["degraded"]
        assert verdict["cycle"]["mode"] in ("pipelined", "batched")
        # the per-tenant SLO specs were evaluated
        tenant_slos = [n for n in verdict["slo"]
                       if n.startswith("tenant_")]
        assert len(tenant_slos) == 3
        assert verdict["push_errors"] == 0


@pytest.fixture(scope="module")
def green_soak(tmp_path_factory):
    """ONE seeded churn soak shared by the green-verdict assertions:
    scheduler sidecar + manager + feeder over real sockets, the full
    observatory sampling it."""
    import dataclasses

    cfg = dataclasses.replace(loadgen.smoke_config(seed=7),
                              duration_s=90.0)
    events = loadgen.generate_trace(cfg)
    workdir = str(tmp_path_factory.mktemp("green-soak"))
    harness = loadgen.SteadyStateHarness(
        cfg, workdir, time_scale=15.0, solve_interval_s=4.0,
        slo_latency_threshold_s=5.0)
    harness.start()
    try:
        verdict = harness.run(events)
        yield harness, verdict
    finally:
        harness.close()


class TestGreenSoak:
    """The acceptance bar's fast deterministic half: a seeded churn soak
    completes with a green steady-state verdict."""

    def test_verdict_is_green(self, green_soak):
        harness, verdict = green_soak
        assert verdict["green"], (verdict["trend"]["leaking"],
                                  verdict["trend"]["drifting"],
                                  verdict["slo_breached"],
                                  verdict["degraded"])
        assert not verdict["trend"]["leaking"]
        assert not verdict["trend"]["drifting"]

    def test_churn_actually_flowed(self, green_soak):
        harness, verdict = green_soak
        assert verdict["push_errors"] == 0
        assert verdict["events_applied"] > 100
        # wall-clock compression note: a compile-heavy early round can
        # burn many virtual seconds, so the floor is conservative
        assert verdict["rounds"] >= 4
        assert verdict["bound"] > 0
        # every watched series had enough samples for a real verdict
        assert verdict["trend"]["verdicts"]["no_data"] == 0

    def test_backlog_and_degraded_time_bounded(self, green_soak):
        harness, verdict = green_soak
        assert verdict["backlog_peak"] <= 64
        assert not verdict["degraded"]

    def test_debug_steady_serves_the_same_verdicts(self, green_soak):
        """Both debug surfaces serve the shared builder's body."""
        from koordinator_tpu.scheduler.services import DebugService

        import time as _time

        harness, verdict = green_soak
        service = DebugService(harness.scheduler)
        # query the post-warmup steady window, the same one the verdict
        # used (the full-run window would re-fit over jit-compilation
        # growth, which is warmup, not steady state)
        window = max(1.0, _time.time() - harness.steady_started_at)
        status, body = service.handle("/debug/steady",
                                      {"window": f"{window}"})
        assert status == 200
        assert body["verdicts"]["leaking"] == 0
        assert {d["series"] for d in body["series"]} == {
            s.series for s in harness.trend.specs}
        assert "slo_breached" in body

    def test_debug_steady_window_validation(self, green_soak):
        from koordinator_tpu.scheduler.services import DebugService

        harness, _ = green_soak
        service = DebugService(harness.scheduler)
        assert service.handle("/debug/steady", {"window": "bogus"})[0] == 400
        assert service.handle("/debug/steady", {"window": "-5"})[0] == 400
        assert service.handle("/debug/steady", {"window": "nan"})[0] == 400


class TestLeakCatches:
    """The other half of the acceptance bar: the SAME harness must flag
    deliberately-injected leaks — a detector that can't catch a planted
    leak proves nothing."""

    def test_thread_leak_is_caught(self, tmp_path):
        import dataclasses

        cfg = dataclasses.replace(loadgen.smoke_config(seed=5),
                                  duration_s=60.0)
        events = loadgen.generate_trace(cfg)
        harness = loadgen.SteadyStateHarness(
            cfg, str(tmp_path), time_scale=15.0, solve_interval_s=2.0,
            slo_latency_threshold_s=5.0,
            inject_thread_leak=True)
        harness.start()
        try:
            verdict = harness.run(events)
        finally:
            harness.close()
        assert any("koord_process_threads" in s
                   for s in verdict["trend"]["leaking"]), verdict["trend"]
        assert not verdict["green"]
        # the leaked workers were released at close: no bleed into
        # other tests
        assert not harness._leaked_threads

    def test_queue_leak_is_caught(self, tmp_path):
        import dataclasses

        cfg = dataclasses.replace(loadgen.smoke_config(seed=6),
                                  duration_s=60.0, arrival_rate=3.0)
        events = loadgen.generate_trace(cfg)
        harness = loadgen.SteadyStateHarness(
            cfg, str(tmp_path), time_scale=15.0, solve_interval_s=2.0,
            slo_latency_threshold_s=5.0,
            inject_queue_leak=True)
        harness.start()
        try:
            verdict = harness.run(events)
        finally:
            harness.close()
        assert "koord_scheduler_pending_pods" in verdict["trend"]["leaking"]
        assert not verdict["green"]


class TestFlightRingSizeFlag:
    """--flight-ring-size satellite: the ring capacity is a flag, and
    round_flight_overwritten_total accounts exactly for the chosen
    size."""

    def test_flag_reaches_the_recorder(self):
        from koordinator_tpu.cmd.binaries import main_koord_scheduler

        asm = main_koord_scheduler(
            ["--disable-leader-election", "--flight-ring-size", "8"])
        try:
            assert asm.component.flight_recorder.capacity == 8
        finally:
            asm.stop()

    def test_overwrites_accounted_against_chosen_size(self):
        from koordinator_tpu import metrics
        from koordinator_tpu.scheduler.flight_recorder import (
            FlightRecorder,
            RoundRecord,
        )

        def make_record(n: int) -> RoundRecord:
            return RoundRecord(
                round=n, trace_id=f"t{n}", start_time=0.0, duration_s=0.01,
                solver="batch", solve_path="incremental", pods=1, placed=1,
                failed=0, suspended=0, degraded=False, staleness_s=0.0,
                dirty_node_frac=0.0, dirty_pod_frac=0.0, solve_wall_s=0.01,
                solve_device_s=0.005)

        rec = FlightRecorder(capacity=8)
        for n in range(20):
            rec.record(make_record(n))
        assert rec.overwrites == 20 - 8
        assert metrics.round_flight_overwritten.value() == 20 - 8
        assert len(rec.records) == 8

    def test_scheduler_rounds_respect_the_flag(self):
        """End to end through the binary assembly: more rounds than the
        ring holds -> the excess is counted, the ring holds exactly the
        flag's worth."""
        from koordinator_tpu import metrics
        from koordinator_tpu.cmd.binaries import main_koord_scheduler

        asm = main_koord_scheduler(
            ["--disable-leader-election", "--flight-ring-size", "4"])
        sched = asm.component
        try:
            for _ in range(10):
                sched.schedule_round()
            assert len(sched.flight_recorder.records) == 4
            assert metrics.round_flight_overwritten.value() == 10 - 4
        finally:
            asm.stop()


class TestTelemetryInBinaries:
    def test_every_binary_registers_self_telemetry(self):
        from koordinator_tpu import metrics
        from koordinator_tpu.cmd.binaries import (
            main_koord_manager,
            main_koord_scheduler,
        )

        sched = main_koord_scheduler(["--disable-leader-election"])
        mgr = main_koord_manager(
            ["--disable-leader-election",
             "--self-telemetry-interval-seconds", "0.05"])
        try:
            # the scheduler samples via the SLO sweep (pre-sample hook)
            sched.component.slo_monitor.sample_once()
            assert metrics.process_threads.value(
                labels={"binary": "koord-scheduler"}) >= 1.0
            # the manager's background thread samples on its own
            import time as _time

            deadline = _time.monotonic() + 5.0
            while (_time.monotonic() < deadline
                   and metrics.process_threads.value(
                       labels={"binary": "koord-manager"}) < 1.0):
                _time.sleep(0.02)
            assert metrics.process_threads.value(
                labels={"binary": "koord-manager"}) >= 1.0
        finally:
            mgr.stop()
            sched.stop()
        assert mgr.telemetry._thread is None   # stop() joined it

    def test_trend_engine_attached_with_window_flag(self):
        from koordinator_tpu.cmd.binaries import main_koord_scheduler

        asm = main_koord_scheduler(
            ["--disable-leader-election",
             "--trend-window-seconds", "900"])
        try:
            assert asm.component.trend_engine is not None
            assert asm.component.trend_engine.window_s == 900.0
            # shares the SLO monitor's cache: one sampling pass feeds both
            assert (asm.component.trend_engine.cache
                    is asm.component.slo_monitor.cache)
        finally:
            asm.stop()


class TestBacklogWatermark:
    def test_binding_backlog_peak_tracks_commits(self):
        import numpy as np

        from koordinator_tpu import metrics
        from koordinator_tpu.api.resources import resource_vector
        from koordinator_tpu.transport.deltasync import StateSyncService

        class SlowBinding:
            service_name = "scheduler"

            def __init__(self):
                self.applied = []

            def node_upsert(self, entry, arrs):
                self.applied.append(entry["name"])

            def note_sync_event(self):
                pass

        service = StateSyncService()
        service.attach_binding(SlowBinding())
        alloc = np.asarray(resource_vector(cpu=1000, memory=1000),
                           np.int32)
        for i in range(5):
            service.upsert_node(f"n{i}", alloc)
        assert metrics.sync_binding_backlog_peak.value() >= 1.0
        assert metrics.sync_binding_backlog.value() == 0.0  # drained
