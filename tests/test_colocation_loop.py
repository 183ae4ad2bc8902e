"""ManagerSyncBinding + ColocationLoop unit coverage (the §3.2 manager
leg; the full three-binary flow lives in test_deployment_sim.py).

Pins the two restart/re-registration behaviors the r5 review caught:
a bootstrap snapshot must restore the colocation formula's usage inputs
(sys_usage/hp_usage ride the merged node_upsert arrays), and a wholesale
node re-upsert must reset the diff-suppression state so the batch
capacity it wiped gets re-pushed.
"""

import numpy as np
import pytest

from koordinator_tpu.api.resources import ResourceDim, resource_vector
from koordinator_tpu.manager.colocation_loop import (
    ColocationLoop,
    ManagerSyncBinding,
)
from koordinator_tpu.manager.noderesource_controller import (
    NodeResourceController,
)
from koordinator_tpu.transport import StateSyncService


class FakeClock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t


def _service_with_node(clock):
    service = StateSyncService()
    service.upsert_node("n0", resource_vector(cpu=16_000, memory=16_384))
    service.update_node_usage(
        "n0",
        resource_vector(cpu=2_000, memory=4_096),
        sys_usage=resource_vector(cpu=500, memory=512),
        hp_usage=resource_vector(cpu=3_000, memory=2_048))
    return service


def _loop(service, clock):
    binding = ManagerSyncBinding(clock=clock)
    service.attach_binding(binding)
    pushes = []

    def push(names, allocatable):
        _rv, rejected = service.update_node_allocatable_run(
            names, allocatable)
        pushes.extend((name, row.copy())
                      for name, row in zip(names, allocatable))
        return rejected

    controller = NodeResourceController(clock=clock)
    return ColocationLoop(controller, binding, push), binding, pushes


def test_bootstrap_replay_restores_formula_inputs():
    """A manager that attaches AFTER the koordlet's report still sees
    sys/hp usage (they ride the merged node_upsert replay): its first
    reconcile must subtract HP.Used instead of over-advertising."""
    clock = FakeClock()
    service = _service_with_node(clock)
    loop, binding, pushes = _loop(service, clock)
    # attach_binding replays nothing retroactively; replay the snapshot
    # by hand the way a bootstrap does
    doc, arrays = service._snapshot()
    from koordinator_tpu.transport.deltasync import (
        StateSyncClient,
        _unpack_event_arrays,
    )

    for entry in doc["events"]:
        from koordinator_tpu.transport.deltasync import _dispatch_event

        _dispatch_event(binding, entry, _unpack_event_arrays(entry, arrays))

    with binding.lock:
        view = binding.nodes["n0"]
        assert view.hp_usage is not None and view.sys_usage is not None
        assert int(view.hp_usage[ResourceDim.CPU]) == 3_000

    assert loop.tick() == 1
    name, alloc = pushes[-1]
    batch = int(alloc[ResourceDim.BATCH_CPU])
    assert 0 < batch < 16_000
    # with HP forgotten the formula would yield ~3,000m more batch
    with binding.lock:
        binding.nodes["n0"].hp_usage = np.zeros_like(
            binding.nodes["n0"].hp_usage)
    loop.tick()
    _, alloc_nohp = pushes[-1]
    assert int(alloc_nohp[ResourceDim.BATCH_CPU]) - batch >= 2_500


def test_reupsert_resets_diff_suppression_and_repushes():
    """node_upsert replaces the stored doc wholesale (wiping batch dims
    from the scheduler's view); the manager must re-push even though its
    own computed value did not change."""
    clock = FakeClock()
    service = _service_with_node(clock)
    loop, binding, pushes = _loop(service, clock)
    # live path: the binding saw the node via attach_binding? no —
    # attach happened after; re-send the node and usage live
    service.upsert_node("n0", resource_vector(cpu=16_000, memory=16_384))
    service.update_node_usage(
        "n0", resource_vector(cpu=2_000, memory=4_096),
        sys_usage=resource_vector(cpu=500, memory=512),
        hp_usage=resource_vector(cpu=3_000, memory=2_048))
    from koordinator_tpu import metrics

    patches_before = metrics.colocation_patches_total.value()
    assert loop.tick() == 1
    assert metrics.colocation_patches_total.value() == patches_before + 1
    first = pushes[-1][1]
    assert int(first[ResourceDim.BATCH_CPU]) > 0
    # steady state: same inputs, no new push
    assert loop.tick() == 0

    # the koordlet re-registers the node (restart): batch dims wiped
    service.upsert_node("n0", resource_vector(cpu=16_000, memory=16_384),
                        usage=resource_vector(cpu=2_000, memory=4_096))
    assert loop.tick() == 1, "re-upsert must defeat diff suppression"
    again = pushes[-1][1]
    assert int(again[ResourceDim.BATCH_CPU]) == int(
        first[ResourceDim.BATCH_CPU])

    # node removal drops both view and record
    service.remove_node("n0")
    assert loop.tick() == 0
    with binding.lock:
        assert "n0" not in binding.nodes
        assert "n0" not in binding.records


def test_stale_metrics_zero_batch_over_the_loop():
    """Degrade mode (noderesource_controller._degraded): when a node's
    usage report goes stale past degradeTimeMinutes, the loop must push
    a ZEROING patch — leaving the last batch capacity advertised on a
    node whose metrics went dark is the over-commit the degrade path
    exists to prevent."""
    clock = FakeClock()
    service = _service_with_node(clock)
    loop, binding, pushes = _loop(service, clock)
    service.upsert_node("n0", resource_vector(cpu=16_000, memory=16_384))
    service.update_node_usage(
        "n0", resource_vector(cpu=2_000, memory=4_096),
        sys_usage=resource_vector(cpu=500, memory=512),
        hp_usage=resource_vector(cpu=3_000, memory=2_048))
    assert loop.tick() == 1
    assert int(pushes[-1][1][ResourceDim.BATCH_CPU]) > 0

    # collectors go dark: 16 minutes pass with no usage refresh
    clock.t += 16 * 60
    assert loop.tick() == 1, "degrade must emit a zeroing patch"
    degraded = pushes[-1][1]
    assert int(degraded[ResourceDim.BATCH_CPU]) == 0
    assert int(degraded[ResourceDim.BATCH_MEMORY]) == 0
    assert int(degraded[ResourceDim.MID_CPU]) == 0
    # base capacity dims are untouched
    assert int(degraded[ResourceDim.CPU]) == 16_000
    # a fresh report recovers the capacity
    service.update_node_usage(
        "n0", resource_vector(cpu=2_000, memory=4_096),
        sys_usage=resource_vector(cpu=500, memory=512),
        hp_usage=resource_vector(cpu=3_000, memory=2_048))
    assert loop.tick() == 1
    assert int(pushes[-1][1][ResourceDim.BATCH_CPU]) > 0


def test_manager_sidecar_reconnects_after_scheduler_restart(tmp_path):
    """The colocation loop must survive a sidecar restart: the manager's
    reconnecting client re-dials + re-bootstraps on the next tick (a
    bare RpcClient would leave the watch dead and batch allocatable
    permanently stale — r5 review finding)."""
    import time

    from koordinator_tpu.cmd.binaries import (
        main_koord_manager,
        main_koord_scheduler,
    )

    sock = str(tmp_path / "reconnect.sock")

    def boot_scheduler():
        asm = main_koord_scheduler([
            "--node-capacity", "8", "--listen-socket", sock,
            "--disable-leader-election"])
        asm.state_sync.upsert_node(
            "n0", resource_vector(cpu=16_000, memory=16_384))
        asm.state_sync.update_node_usage(
            "n0", resource_vector(cpu=2_000, memory=4_096),
            sys_usage=resource_vector(cpu=500, memory=512),
            hp_usage=resource_vector(cpu=3_000, memory=2_048))
        return asm

    sched = boot_scheduler()
    manager_asm = None
    try:
        manager_asm = main_koord_manager(
            ["--scheduler-sidecar-addr", sock])
        manager = manager_asm.component
        # lazy dial: the first tick bootstraps the watch AND reconciles
        deadline = time.monotonic() + 10
        pushed = 0
        while pushed == 0 and time.monotonic() < deadline:
            pushed = manager.colocation_loop.tick()
            time.sleep(0.05)
        assert pushed == 1

        # sidecar dies; ticks must not crash, failures are counted
        sched.stop()
        time.sleep(0.1)
        manager.colocation_loop.tick()
        assert (manager.colocation_loop.connect_failures
                + manager.colocation_loop.push_failures) >= 1

        # a fresh sidecar comes up on the same socket: the next tick
        # re-dials, re-bootstraps (full snapshot: the new service's rv
        # restarted), and pushes batch capacity to the NEW scheduler
        sched = boot_scheduler()
        deadline = time.monotonic() + 10
        pushed = 0
        while pushed == 0 and time.monotonic() < deadline:
            pushed = manager.colocation_loop.tick()
            time.sleep(0.1)
        assert pushed == 1, "loop never recovered after sidecar restart"
        stored = sched.state_sync.nodes["n0"]["arrays"]
        assert int(stored["allocatable"][ResourceDim.BATCH_CPU]) > 0
    finally:
        if manager_asm is not None:
            manager_asm.component.stop()
        sched.stop()


def test_manager_boots_before_scheduler(tmp_path):
    """Deploy order must not matter: a manager assembled while the
    scheduler sidecar is still down ticks with counted failures instead
    of crashing, then picks up the loop when the sidecar appears."""
    import time

    from koordinator_tpu.cmd.binaries import (
        main_koord_manager,
        main_koord_scheduler,
    )

    sock = str(tmp_path / "order.sock")
    manager_asm = main_koord_manager(["--scheduler-sidecar-addr", sock])
    manager = manager_asm.component
    sched = None
    try:
        assert manager.colocation_loop.tick() == 0
        assert manager.colocation_loop.connect_failures == 1

        sched = main_koord_scheduler([
            "--node-capacity", "8", "--listen-socket", sock,
            "--disable-leader-election"])
        sched.state_sync.upsert_node(
            "n0", resource_vector(cpu=16_000, memory=16_384))
        sched.state_sync.update_node_usage(
            "n0", resource_vector(cpu=2_000, memory=4_096),
            sys_usage=resource_vector(cpu=500, memory=512),
            hp_usage=resource_vector(cpu=3_000, memory=2_048))
        deadline = time.monotonic() + 10
        pushed = 0
        while pushed == 0 and time.monotonic() < deadline:
            pushed = manager.colocation_loop.tick()
            time.sleep(0.05)
        assert pushed == 1
        stored = sched.state_sync.nodes["n0"]["arrays"]
        assert int(stored["allocatable"][ResourceDim.BATCH_CPU]) > 0
    finally:
        manager_asm.component.stop()
        if sched is not None:
            sched.stop()


def test_wire_fed_hp_request_aggregates_feed_calculate_policies():
    """maxUsageRequest/request policies on wire-fed records: without the
    hp_request/hp_max_used_req aggregates on the node_usage report the
    policy inputs were silently 0 and batch capacity over-advertised by
    the whole HP request footprint."""
    from koordinator_tpu.manager.sloconfig import ColocationConfig

    clock = FakeClock()
    config = ColocationConfig(enable=True,
                              cpu_calculate_policy="maxUsageRequest",
                              memory_calculate_policy="request")

    def run(with_aggregates: bool):
        service = StateSyncService()
        service.upsert_node("n0", resource_vector(cpu=16_000, memory=16_384))
        kw = {}
        if with_aggregates:
            kw = dict(
                hp_request=resource_vector(cpu=8_000, memory=9_000),
                hp_max_used_req=resource_vector(cpu=9_000, memory=10_000))
        service.update_node_usage(
            "n0", resource_vector(cpu=2_000, memory=4_096),
            sys_usage=resource_vector(cpu=500, memory=512),
            hp_usage=resource_vector(cpu=3_000, memory=2_048), **kw)
        binding = ManagerSyncBinding(clock=clock)
        service.attach_binding(binding)
        # re-send live (attach_binding has no retroactive replay)
        service.update_node_usage(
            "n0", resource_vector(cpu=2_000, memory=4_096),
            sys_usage=resource_vector(cpu=500, memory=512),
            hp_usage=resource_vector(cpu=3_000, memory=2_048), **kw)
        pushes = []
        loop = ColocationLoop(NodeResourceController(config, clock=clock),
                              binding,
                              lambda names, alloc: pushes.extend(alloc))
        # the node view needs allocatable: replay the upsert live too
        service.upsert_node("n0", resource_vector(cpu=16_000, memory=16_384))
        service.update_node_usage(
            "n0", resource_vector(cpu=2_000, memory=4_096),
            sys_usage=resource_vector(cpu=500, memory=512),
            hp_usage=resource_vector(cpu=3_000, memory=2_048), **kw)
        assert loop.tick() == 1
        return pushes[-1]

    with_agg = run(True)
    without = run(False)
    # maxUsageRequest (cpu): 9,000m of per-pod max(request, usage) must be
    # carved out instead of 0 — the with-aggregates push advertises less
    assert (int(without[ResourceDim.BATCH_CPU])
            - int(with_agg[ResourceDim.BATCH_CPU])) >= 8_000
    # request (memory): the 9,000 MiB HP request footprint likewise
    assert (int(without[ResourceDim.BATCH_MEMORY])
            - int(with_agg[ResourceDim.BATCH_MEMORY])) >= 8_000


def test_bootstrap_replay_preserves_report_time_for_degrade():
    """A manager that bootstraps AFTER the koordlet's last report must
    date the usage by the REPORT timestamp riding the merged doc, not by
    apply time: a stale node is then zeroed on the first reconcile
    instead of getting a fresh degrade window per restart."""
    clock = FakeClock(t=1_000.0)
    service = StateSyncService()
    service.upsert_node("n0", resource_vector(cpu=16_000, memory=16_384))
    service.update_node_usage(
        "n0", resource_vector(cpu=2_000, memory=4_096),
        sys_usage=resource_vector(cpu=500, memory=512),
        hp_usage=resource_vector(cpu=3_000, memory=2_048),
        report_time=1_000.0)

    # 20 minutes later (past degradeTimeMinutes=15) a fresh manager
    # attaches and replays the bootstrap snapshot
    clock.t = 1_000.0 + 20 * 60
    binding = ManagerSyncBinding(clock=clock)
    doc, arrays = service._snapshot()
    from koordinator_tpu.transport.deltasync import (
        _dispatch_event,
        _unpack_event_arrays,
    )

    for entry in doc["events"]:
        _dispatch_event(binding, entry, _unpack_event_arrays(entry, arrays))
    with binding.lock:
        assert binding.nodes["n0"].usage_time == 1_000.0

    pushes = []
    loop = ColocationLoop(NodeResourceController(clock=clock), binding,
                          lambda names, alloc: pushes.extend(alloc))
    assert loop.tick() == 1, "stale node must push a zeroing patch"
    zeroed = pushes[-1]
    assert int(zeroed[ResourceDim.BATCH_CPU]) == 0
    assert int(zeroed[ResourceDim.BATCH_MEMORY]) == 0

    # a FRESH report (new report_time) recovers capacity
    service.attach_binding(binding)
    service.update_node_usage(
        "n0", resource_vector(cpu=2_000, memory=4_096),
        sys_usage=resource_vector(cpu=500, memory=512),
        hp_usage=resource_vector(cpu=3_000, memory=2_048),
        report_time=clock.t)
    assert loop.tick() == 1
    assert int(pushes[-1][ResourceDim.BATCH_CPU]) > 0


# -- the run seam: push_fn(names, allocatable) --------------------------------

def _cluster_loop(n, clock, push=None):
    """A service of ``n`` reported nodes watched in process, and a loop
    whose push commits to it (or is ``push``) and logs its calls."""
    service = StateSyncService(retention=4 * n + 64)
    binding = ManagerSyncBinding(clock=clock)
    service.attach_binding(binding)
    for i in range(n):
        service.upsert_node(f"n{i}",
                            resource_vector(cpu=16_000 + i, memory=16_384))
        service.update_node_usage(
            f"n{i}", resource_vector(cpu=2_000, memory=4_096),
            sys_usage=resource_vector(cpu=500, memory=512),
            hp_usage=resource_vector(cpu=3_000 + i % 7, memory=2_048))
    calls = []

    def commit(names, allocatable):
        calls.append((list(names), np.array(allocatable)))
        return service.update_node_allocatable_run(names, allocatable)[1]

    def logged(names, allocatable):
        calls.append((list(names), np.array(allocatable)))
        return push(names, allocatable)

    loop = ColocationLoop(NodeResourceController(clock=clock), binding,
                          commit if push is None else logged)
    return service, binding, loop, calls


@pytest.mark.parametrize("n", [1, 1_024, 1_025, 2_500])
def test_a_tick_of_n_patches_goes_out_in_frames_of_at_most_1024(n):
    from koordinator_tpu import metrics, tracing
    from koordinator_tpu.transport.wire import STATE_PUSH_RUN_MAX

    clock = FakeClock()
    service, binding, loop, calls = _cluster_loop(n, clock)
    base = {name: view.allocatable.copy()
            for name, view in binding.nodes.items()}
    exporter = tracing.InMemoryExporter()
    tracing.TRACER.add_exporter(exporter)
    try:
        assert loop.tick() == n
    finally:
        tracing.TRACER.remove_exporter(exporter)
    frames = -(-n // STATE_PUSH_RUN_MAX)
    assert [len(names) for names, _ in calls] == (
        [STATE_PUSH_RUN_MAX] * (n // STATE_PUSH_RUN_MAX)
        + [n % STATE_PUSH_RUN_MAX] * (n % STATE_PUSH_RUN_MAX > 0))
    assert len(calls) == frames
    # patch order is the view's order, across the frames
    assert [name for names, _ in calls for name in names] == [
        f"n{i}" for i in range(n)]
    written = [int(d) for d in (
        ResourceDim.BATCH_CPU, ResourceDim.BATCH_MEMORY,
        ResourceDim.MID_CPU, ResourceDim.MID_MEMORY)]
    kept = [d for d in range(len(base["n0"])) if d not in written]
    for names, rows in calls:
        assert rows.shape == (len(names), len(base["n0"]))
        assert rows.dtype == np.int32
        for name, row in zip(names, rows):
            assert row[kept].tolist() == base[name][kept].tolist()
            assert row[ResourceDim.BATCH_CPU] > 0
            # what was sent is what the service holds
            assert service.nodes[name]["arrays"][
                "allocatable"].tolist() == row.tolist()
    assert metrics.colocation_push_frames_total.value() == frames
    assert metrics.colocation_patches_total.value() == n
    assert metrics.colocation_push_failures_total.value() == 0
    # one manager.colocation_push span a frame, naming the tick and the
    # frame's first and last node
    spans = exporter.find(name="manager.colocation_push")
    assert [(s.attributes["tick"], s.attributes["first"],
             s.attributes["last"], s.attributes["n"]) for s in spans] == [
        (1, names[0], names[-1], len(names)) for names, _ in calls]
    # steady state: nothing to say, no frame
    assert loop.tick() == 0 and len(calls) == frames


@pytest.mark.parametrize("case", ["fresh_patches", "zeroing_patches"])
def test_a_frame_with_no_reply_fails_its_names_and_the_next_tick_resends(
        case):
    """Two frames, the second's call raises: exactly its names have their
    diff state reset (last_degraded too: the zeroing-patch case) and are
    sent again by the next tick; the first frame's are not."""
    from koordinator_tpu import metrics
    from koordinator_tpu.transport.wire import STATE_PUSH_RUN_MAX

    n, lost = STATE_PUSH_RUN_MAX + 6, 6
    clock = FakeClock()
    down = [False]
    service = [None]

    def push(names, allocatable):
        if down[0] and names[0] == f"n{STATE_PUSH_RUN_MAX}":
            raise ConnectionError("sidecar wedged")
        return service[0].update_node_allocatable_run(names, allocatable)[1]

    service[0], binding, loop, calls = _cluster_loop(n, clock, push)
    if case == "zeroing_patches":
        assert loop.tick() == n          # capacity advertised
        clock.t += 16 * 60               # every report goes stale
        del calls[:]
    down[0] = True
    assert loop.tick() == n - lost
    tail = [f"n{i}" for i in range(STATE_PUSH_RUN_MAX, n)]
    assert [names for names, _ in calls][1] == tail
    assert loop.push_failures == lost
    assert metrics.colocation_push_failures_total.value() == lost
    for name, record in binding.records.items():
        failed = name in tail
        assert (record.last_batch_cpu == -1) == failed, name
        assert (record.last_device_resources is None) == failed
        if case == "zeroing_patches":
            assert record.last_degraded == (not failed), name
            stored = service[0].nodes[name]["arrays"]["allocatable"]
            assert (int(stored[ResourceDim.BATCH_CPU]) == 0) == (not failed)
    down[0] = False
    del calls[:]
    assert loop.tick() == lost
    assert [names for names, _ in calls] == [tail]
    if case == "zeroing_patches":
        assert not calls[0][1][:, int(ResourceDim.BATCH_CPU)].any()
        assert all(int(service[0].nodes[name]["arrays"]["allocatable"][
            ResourceDim.BATCH_CPU]) == 0 for name in tail)
    assert loop.tick() == 0


def test_a_rejected_name_fails_alone():
    from koordinator_tpu import metrics

    clock = FakeClock()
    turn_away = [True]

    def push(names, allocatable):
        return [("n3", "unknown node")] if turn_away[0] else []

    _service, binding, loop, calls = _cluster_loop(8, clock, push)
    assert loop.tick() == 7
    assert loop.push_failures == 1
    assert metrics.colocation_patches_total.value() == 7
    assert metrics.colocation_push_frames_total.value() == 1
    assert [name for name, record in binding.records.items()
            if record.last_batch_cpu == -1] == ["n3"]
    turn_away[0] = False
    del calls[:]
    assert loop.tick() == 1
    assert [names for names, _ in calls] == [["n3"]]


def test_sidecar_push_sends_one_run_frame_and_reports_the_rejected(tmp_path):
    """The manager binary's wiring: one run-form STATE_PUSH a call; a name
    the sidecar does not hold comes back rejected and the reply's resync
    re-HELLOs the watch."""
    from koordinator_tpu.cmd.binaries import ReconnectingSidecarClient
    from koordinator_tpu.manager.colocation_loop import sidecar_push
    from koordinator_tpu.transport import RpcServer, StateSyncClient

    server = RpcServer(str(tmp_path / "push.sock"))
    service = StateSyncService()
    service.attach(server)
    server.start()
    for name in ("n0", "n1"):
        service.upsert_node(name, resource_vector(cpu=16_000, memory=16_384))
    binding = ManagerSyncBinding()
    sync = StateSyncClient(binding)
    hellos = []

    def bootstrap(client):
        hellos.append(1)
        sync.bind_client(client)
        sync.bootstrap(client)

    sidecar = ReconnectingSidecarClient(
        server.path, on_push=sync.on_push, on_connect=bootstrap)
    try:
        push = sidecar_push(sidecar)
        rows = np.stack([resource_vector(cpu=16_000, memory=16_384,
                                         batch_cpu=1_000 + i)
                         for i in range(3)])
        assert push(["n0", "n1"], rows[:2]) == []
        assert sync.rv == service.rv == 4, "reply overtook the echo"
        assert len(hellos) == 1
        assert push(["n0", "ghost", "n1"], rows) == [
            ["ghost", "unknown node"]]
        assert len(hellos) == 2 and sidecar.resyncs == 1
        assert service.rv == 6
        for name, row in (("n0", rows[0]), ("n1", rows[2])):
            assert binding.nodes[name].allocatable.tolist() == row.tolist()
    finally:
        sidecar.close()
        server.stop()
