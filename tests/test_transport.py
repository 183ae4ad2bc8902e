"""Wire protocol + delta sync + RPC services (koordinator_tpu/transport/)
vs the reference's deployment seams: apiserver watch streams (LIST+WATCH,
410-Gone resync), the hook gRPC protocol (api.proto:148), and the sidecar
solve bridge (SURVEY.md §7 step 4)."""

import collections
import threading
import time

import numpy as np
import pytest

from koordinator_tpu.api.resources import NUM_RESOURCE_DIMS, resource_vector
from koordinator_tpu.ops.assignment import ScoringConfig
from koordinator_tpu.scheduler import ClusterSnapshot, Scheduler
from koordinator_tpu.transport import (
    RpcClient,
    RpcError,
    RpcServer,
    StateSyncClient,
    StateSyncService,
)
from koordinator_tpu.transport.deltasync import (
    DeltaLog,
    ResyncRequired,
    SchedulerBinding,
    _pack_events,
)
from koordinator_tpu.transport.services import (
    HookService,
    SolveService,
    hook_remote,
    solve_remote,
)
from koordinator_tpu.transport.wire import (
    MIN_PROTOCOL_VERSION,
    PROTOCOL_VERSION,
    FrameType,
    decode_payload,
    encode_payload,
)

R = NUM_RESOURCE_DIMS


def test_payload_roundtrip_with_arrays():
    doc = {"kind": "x", "names": ["a", "b"]}
    arrays = {
        "alloc": np.arange(2 * R, dtype=np.int32).reshape(2, R),
        "mask": np.asarray([True, False]),
        "scalar": np.int64(7).reshape(()),
    }
    out_doc, out_arrays = decode_payload(encode_payload(doc, arrays))
    assert out_doc == doc
    assert np.array_equal(out_arrays["alloc"], arrays["alloc"])
    assert out_arrays["alloc"].dtype == np.int32
    assert np.array_equal(out_arrays["mask"], arrays["mask"])
    assert out_arrays["scalar"].reshape(()).item() == 7


def test_delta_log_window_and_resync():
    log = DeltaLog(retention=3)
    for rv in range(1, 6):
        log.append(rv, {"kind": "e", "n": rv}, {})
    assert [e["n"] for _, e, _ in log.since(3)] == [4, 5]
    assert log.since(5) == []
    with pytest.raises(ResyncRequired):
        log.since(0)   # window starts at rv 3


@pytest.fixture
def rpc(tmp_path):
    server = RpcServer(str(tmp_path / "koord.sock"))
    clients = []
    try:
        yield server, clients
    finally:
        for c in clients:
            c.close()
        server.stop()


def connect(server, clients, **kw):
    client = RpcClient(server.path, **kw)
    client.connect()
    clients.append(client)
    return client


def test_rpc_call_and_error(rpc):
    server, clients = rpc

    def echo(doc, arrays):
        if doc.get("boom"):
            raise ValueError("kaput")
        out = {"arr": arrays["arr"] * 2} if "arr" in arrays else None
        return {"echo": doc["msg"]}, out

    server.register(FrameType.SOLVE_REQUEST, echo)
    server.start()
    client = connect(server, clients)
    ftype, doc, arrays = client.call(
        FrameType.SOLVE_REQUEST, {"msg": "hi"},
        {"arr": np.asarray([1, 2], np.int32)})
    assert ftype is FrameType.SOLVE_RESPONSE
    assert doc == {"echo": "hi"}
    assert arrays["arr"].tolist() == [2, 4]
    with pytest.raises(RpcError, match="kaput"):
        client.call(FrameType.SOLVE_REQUEST, {"msg": "x", "boom": True})
    # the connection survives handler errors
    _, doc, _ = client.call(FrameType.SOLVE_REQUEST, {"msg": "still up"})
    assert doc == {"echo": "still up"}


def mk_scheduler():
    snap = ClusterSnapshot(capacity=16)
    cfg = ScoringConfig.default().replace(
        usage_thresholds=np.zeros(R, np.int32),
        estimator_defaults=np.zeros(R, np.int32))
    return Scheduler(snap, config=cfg)


def wait_until(pred, timeout=5.0):
    deadline = time.monotonic() + timeout
    while not pred() and time.monotonic() < deadline:
        time.sleep(0.005)
    assert pred(), "condition not reached in time"


def test_sync_snapshot_deltas_and_solve_end_to_end(rpc):
    server, clients = rpc
    service = StateSyncService()
    service.attach(server)
    # pre-existing state before any solver connects
    service.upsert_node("n1", resource_vector(cpu=16_000, memory=65_536))
    service.add_pod("p1", resource_vector(cpu=1_000, memory=1_024))

    sched = mk_scheduler()
    SolveService(sched).attach(server)
    server.start()

    sync = StateSyncClient(SchedulerBinding(sched))
    client = connect(server, clients, on_push=sync.on_push)
    applied = sync.bootstrap(client)
    assert applied == 2 and sync.rv == service.rv

    result = solve_remote(client)
    assert result["assignments"] == {"p1": "n1"}

    # live watch: push a node and a pod, solver applies without polling
    service.upsert_node("n2", resource_vector(cpu=16_000, memory=65_536))
    service.add_pod("p2", resource_vector(cpu=1_000, memory=1_024),
                    node_selector={})
    wait_until(lambda: sync.rv == service.rv)
    result = solve_remote(client)
    assert "p2" in result["assignments"]

    # pod deletion flows too
    service.add_pod("p3", resource_vector(cpu=99_000, memory=1))
    wait_until(lambda: sync.rv == service.rv)
    result = solve_remote(client)
    assert "p3" in result["failures"]
    service.remove_pod("p3")
    wait_until(lambda: sync.rv == service.rv)
    assert "p3" not in sched.pending


def test_delta_burst_within_retention_survives_the_wire(rpc):
    """A push burst the delta log could replay WITHOUT a full resync
    must not poison the connection first: r5's deltasync bench caught a
    1,024-event NodeMetric burst overflowing the old 256-deep per-conn
    send queue at event 256 (the tight producer loop starves the sender
    thread of GIL slices), silently killing the watch.  The live stream
    now holds one slot of that queue and the burst waits in the log."""
    server, clients = rpc
    service = StateSyncService()
    service.attach(server)
    server.start()
    service.upsert_node("n1", resource_vector(cpu=16_000, memory=65_536))

    sched = mk_scheduler()
    sync = StateSyncClient(SchedulerBinding(sched))
    client = connect(server, clients, on_push=sync.on_push)
    sync.bootstrap(client)

    n_burst = 1_024
    for i in range(n_burst):
        service.update_node_usage(
            "n1", resource_vector(cpu=100 + i, memory=1_024))
    wait_until(lambda: sync.rv == service.rv, timeout=30.0)
    assert client.connected, "burst poisoned the connection"
    assert sync.applied >= n_burst
    spec = sched.snapshot.node_specs["n1"]
    assert spec.usage[0] == 100 + n_burst - 1   # last update won


def test_sync_reconnect_resumes_from_rv(rpc):
    server, clients = rpc
    service = StateSyncService()
    service.attach(server)
    server.start()
    service.upsert_node("n1", resource_vector(cpu=16_000, memory=65_536))

    sched = mk_scheduler()
    sync = StateSyncClient(SchedulerBinding(sched))
    client = connect(server, clients, on_push=sync.on_push)
    sync.bootstrap(client)
    rv_before = sync.rv

    client.close()   # solver restarts its connection
    # events land while disconnected
    service.add_pod("p1", resource_vector(cpu=1_000, memory=1_024))
    service.upsert_node("n2", resource_vector(cpu=16_000, memory=65_536))

    client2 = connect(server, clients, on_push=sync.on_push)
    applied = sync.bootstrap(client2)
    assert applied == 2                    # only the missed deltas replayed
    assert sync.rv == service.rv > rv_before
    assert "p1" in sched.pending
    assert "n2" in sched.snapshot.node_index


def test_hello_detects_service_restart_despite_rv_collision(tmp_path):
    """A restarted service resets its rv counter; if the new counter
    happens to EQUAL the client's last_rv, an rv-only HELLO would return
    a bare ACK and the client would keep a permanently stale view.  The
    instance (boot-epoch) id in the handshake forces the full snapshot
    across incarnations regardless of rv."""
    sock = str(tmp_path / "epoch.sock")

    def boot(node_name):
        server = RpcServer(sock)
        service = StateSyncService()
        service.attach(server)
        server.start()
        service.upsert_node(node_name,
                            resource_vector(cpu=8_000, memory=8_192))
        return server, service

    server1, service1 = boot("n-old")
    sched = mk_scheduler()
    sync = StateSyncClient(SchedulerBinding(sched))
    client = RpcClient(sock, on_push=sync.on_push)
    client.connect()
    sync.bootstrap(client)
    assert sync.rv == service1.rv == 1
    assert sync.instance == service1.instance
    client.close()
    server1.stop()

    # fresh incarnation, DIFFERENT state, same rv counter value
    server2, service2 = boot("n-new")
    assert service2.rv == 1 and service2.instance != service1.instance
    client2 = RpcClient(sock, on_push=sync.on_push)
    client2.connect()
    applied = sync.bootstrap(client2)
    assert applied == 1, "rv collision returned ACK instead of snapshot"
    assert sync.instance == service2.instance
    assert sorted(sched.snapshot.node_index) == ["n-new"]
    # same incarnation, same rv: NOW the ACK shortcut is correct
    assert sync.bootstrap(client2) == 0
    client2.close()
    server2.stop()


def test_sync_falls_back_to_snapshot_beyond_retention(rpc):
    server, clients = rpc
    service = StateSyncService(retention=2)
    service.attach(server)
    server.start()
    service.upsert_node("n1", resource_vector(cpu=16_000, memory=65_536))

    sched = mk_scheduler()
    sync = StateSyncClient(SchedulerBinding(sched))
    client = connect(server, clients, on_push=sync.on_push)
    sync.bootstrap(client)
    client.close()

    for i in range(5):   # blow past the 2-event retention window
        service.upsert_node(f"m{i}", resource_vector(cpu=8_000, memory=8_192))
    service.remove_node("n1")

    client2 = connect(server, clients, on_push=sync.on_push)
    sync.bootstrap(client2)
    assert sync.rv == service.rv
    assert "n1" not in sched.snapshot.node_index     # full resync state
    assert all(f"m{i}" in sched.snapshot.node_index for i in range(5))


def test_sync_replay_overlap_is_idempotent(rpc):
    server, clients = rpc
    service = StateSyncService()
    service.attach(server)
    server.start()
    service.add_pod("p1", resource_vector(cpu=1_000, memory=1_024))

    sched = mk_scheduler()
    sync = StateSyncClient(SchedulerBinding(sched))
    client = connect(server, clients, on_push=sync.on_push)
    sync.bootstrap(client)
    rev = sched._pending_rev
    # a duplicated HELLO (e.g. overlap between push and replay) re-sends
    # everything; the rv guard must drop it without touching the queue
    from koordinator_tpu.transport.wire import PROTOCOL_VERSION

    ftype, doc, arrays = client.call(
        FrameType.HELLO, {"last_rv": 0, "proto": PROTOCOL_VERSION})
    assert ftype is FrameType.DELTA
    sync._apply(doc, arrays)
    assert sync.skipped >= 1
    assert sched._pending_rev == rev      # no spurious cache invalidation


def test_hook_rpc_roundtrip_and_fail_open(rpc):
    from koordinator_tpu.runtimeproxy import (
        Dispatcher, HookRequest, HookResponse, HookType)

    server, clients = rpc
    dispatcher = Dispatcher()

    class BvtServer:
        def handle(self, hook, request):
            return HookResponse(
                annotations={"koordinator.sh/bvt": "2"},
                envs={"SEEN": request.pod_meta.get("uid", "")})

    dispatcher.register(BvtServer(), [HookType.PRE_RUN_POD_SANDBOX])
    HookService(dispatcher).attach(server)
    server.start()
    client = connect(server, clients)

    out = hook_remote(client, HookType.PRE_RUN_POD_SANDBOX,
                      HookRequest(pod_meta={"uid": "u1"}))
    assert out["annotations"]["koordinator.sh/bvt"] == "2"
    assert out["envs"]["SEEN"] == "u1"

    client.close()
    assert hook_remote(client, HookType.PRE_RUN_POD_SANDBOX,
                       HookRequest()) is None      # fail-open
    with pytest.raises(RpcError):
        hook_remote(client, HookType.PRE_RUN_POD_SANDBOX,
                    HookRequest(), fail_open=False)


def test_service_restart_with_lower_rv_forces_snapshot(rpc):
    # the service restarts (rv counter resets); a client whose rv is AHEAD
    # must get a snapshot, not an empty delta that strands it skipping
    # every future event
    server, clients = rpc
    service = StateSyncService()
    service.attach(server)
    server.start()
    service.upsert_node("n1", resource_vector(cpu=16_000, memory=65_536))

    sched = mk_scheduler()
    sync = StateSyncClient(SchedulerBinding(sched))
    client = connect(server, clients, on_push=sync.on_push)
    sync.bootstrap(client)
    sync.rv = 100    # simulate: previous service instance had rv 100
    applied = sync.bootstrap(client)
    assert applied == 1                  # snapshot re-applied
    assert sync.rv == service.rv == 1    # rv dropped to the new authority
    # and future events apply instead of being skipped
    service.add_pod("p1", resource_vector(cpu=1_000, memory=1_024))
    wait_until(lambda: "p1" in sched.pending)


def test_concurrent_mutations_solves_and_pushes():
    # the race-stress version of the sidecar wiring: one thread mutates the
    # informer state while another runs solve RPCs; the scheduler lock and
    # rv ordering must keep every pod accounted exactly once
    import tempfile, os

    d = tempfile.mkdtemp()
    server = RpcServer(os.path.join(d, "s.sock"))
    service = StateSyncService()
    service.attach(server)
    sched = mk_scheduler()
    SolveService(sched).attach(server)
    server.start()
    sync = StateSyncClient(SchedulerBinding(sched))
    client = RpcClient(server.path, on_push=sync.on_push, timeout=60)
    client.connect()
    try:
        service.upsert_node("n1", resource_vector(cpu=100_000, memory=65_536))
        sync.bootstrap(client)

        N = 30
        def mutate():
            for i in range(N):
                service.add_pod(f"p{i}",
                                resource_vector(cpu=100, memory=16))

        th = threading.Thread(target=mutate)
        th.start()
        assigned = {}
        for _ in range(50):
            result = solve_remote(client)
            assigned.update(result["assignments"])
            if len(assigned) == N and not th.is_alive():
                break
            time.sleep(0.01)
        th.join()
        wait_until(lambda: sync.rv == service.rv)
        result = solve_remote(client)
        assigned.update(result["assignments"])
        assert len(assigned) == N        # every pod placed exactly once
        assert not sched.pending
    finally:
        client.close()
        server.stop()


def test_bound_pod_delete_releases_reservation_and_quota(rpc):
    from koordinator_tpu.quota.tree import QuotaTree, UNBOUNDED

    server, clients = rpc
    service = StateSyncService()
    service.attach(server)
    server.start()

    snap = ClusterSnapshot(capacity=16)
    tree = QuotaTree(
        total_resource=resource_vector(cpu=16_000, memory=65_536).astype("int64"))
    tree.add("team", min=resource_vector(cpu=1_000).astype("int64"),
             max=np.full(R, UNBOUNDED, "int64"))
    cfg = ScoringConfig.default().replace(
        usage_thresholds=np.zeros(R, np.int32),
        estimator_defaults=np.zeros(R, np.int32))
    sched = Scheduler(snap, config=cfg, quota_tree=tree)
    SolveService(sched).attach(server)

    sync = StateSyncClient(SchedulerBinding(sched))
    client = connect(server, clients, on_push=sync.on_push)
    sync.bootstrap(client)

    service.upsert_node("n1", resource_vector(cpu=16_000, memory=65_536))
    service.add_pod("p1", resource_vector(cpu=16_000, memory=1_024),
                    quota="team")
    wait_until(lambda: sync.rv == service.rv)
    result = solve_remote(client)
    assert result["assignments"] == {"p1": "n1"}
    assert tree.nodes["team"].used[0] == 16_000

    # p1 completes: the informer delete must free the node AND the quota
    service.remove_pod("p1")
    wait_until(lambda: tree.nodes["team"].used[0] == 0)
    assert "p1" not in sched.bound
    service.add_pod("p2", resource_vector(cpu=16_000, memory=1_024),
                    quota="team")
    wait_until(lambda: sync.rv == service.rv)
    result = solve_remote(client)
    assert result["assignments"] == {"p2": "n1"}   # capacity was released


def test_snapshot_resync_releases_bound_state(rpc):
    server, clients = rpc
    service = StateSyncService(retention=1)
    service.attach(server)
    server.start()

    sched = mk_scheduler()
    binds = []
    sched.bind_fn = lambda p, n: binds.append(p)
    SolveService(sched).attach(server)
    sync = StateSyncClient(SchedulerBinding(sched))

    service.upsert_node("n1", resource_vector(cpu=16_000, memory=65_536))
    service.add_pod("p1", resource_vector(cpu=16_000, memory=1_024))
    # dialed after the two events: a connection listed while they commit
    # may be a whole 1-event log behind before its sender gets a turn
    client = connect(server, clients, on_push=sync.on_push)
    sync.bootstrap(client)
    solve_remote(client)
    assert "p1" in sched.bound

    client.close()
    for i in range(4):   # push far past the 1-event retention window
        service.upsert_node(f"m{i}", resource_vector(cpu=8_000, memory=8_192))

    client2 = connect(server, clients, on_push=sync.on_push)
    sync.bootstrap(client2)     # snapshot resync: restart semantics
    assert not sched.bound      # bound state released with its reservation
    result = solve_remote(client2)
    assert result["assignments"] == {"p1": "n1"}   # re-placed cleanly


def test_reservation_sync_over_the_wire(rpc):
    """Reservation CRs ride the delta protocol: upsert places a reservation
    (hidden capacity), an owner pod draws from it, removal frees it."""
    server, clients = rpc
    service = StateSyncService()
    service.attach(server)
    server.start()

    sched = mk_scheduler()
    SolveService(sched).attach(server)
    sync = StateSyncClient(SchedulerBinding(sched))
    client = connect(server, clients, on_push=sync.on_push)
    sync.bootstrap(client)

    service.upsert_node("n1", resource_vector(cpu=10_000, memory=65_536))
    service.upsert_reservation(
        "rsv-a", resource_vector(cpu=8_000, memory=8_192).astype("int64"),
        owners=[{"labels": {"app": "web"}}])
    wait_until(lambda: sync.rv == service.rv)
    solve_remote(client)                   # round: reserve-pod places
    assert sched.reservations.get("rsv-a").node == "n1"

    # reserved capacity hidden from non-owners pushed over the wire
    service.add_pod("other", resource_vector(cpu=4_000, memory=1_024))
    wait_until(lambda: sync.rv == service.rv)
    result = solve_remote(client)
    assert "other" in result["failures"]

    # ...but an owner pod (labels ride POD_ADD) draws from it
    service.add_pod("web-1", resource_vector(cpu=6_000, memory=1_024),
                    labels={"app": "web"})
    wait_until(lambda: sync.rv == service.rv)
    result = solve_remote(client)
    assert result["assignments"].get("web-1") == "n1"
    assert sched.reservations.get("rsv-a").allocated[0] == 6_000
    service.remove_pod("web-1")
    wait_until(lambda: "web-1" not in sched.bound)

    # removal over the wire frees the capacity
    service.remove_reservation("rsv-a")
    wait_until(lambda: sync.rv == service.rv)
    result = solve_remote(client)
    assert result["assignments"].get("other") == "n1"


def test_reservation_in_snapshot_resync(rpc):
    # a fresh client bootstraps reservations from the snapshot too
    server, clients = rpc
    service = StateSyncService()
    service.attach(server)
    service.upsert_node("n1", resource_vector(cpu=10_000, memory=65_536))
    service.upsert_reservation(
        "rsv-a", resource_vector(cpu=6_000, memory=4_096).astype("int64"),
        owners=[{"labels": {"app": "web"}}])
    server.start()

    sched = mk_scheduler()
    SolveService(sched).attach(server)
    sync = StateSyncClient(SchedulerBinding(sched))
    client = connect(server, clients, on_push=sync.on_push)
    sync.bootstrap(client)
    solve_remote(client)
    assert sched.reservations.get("rsv-a").node == "n1"


def test_fine_grained_registries_ride_node_sync(rpc):
    """NRT annotations + Device inventory on NODE_UPSERT register the
    client scheduler's CPU/device managers, so wire-synced LSR and GPU
    pods get real fine-grained allocations (the deployment path)."""
    from koordinator_tpu.api.qos import QoSClass
    from koordinator_tpu.koordlet.nodetopo import NodeTopology, NUMAZone
    from koordinator_tpu.koordlet.system import procfs
    from koordinator_tpu.scheduler.cpu_manager import CPUManager
    from koordinator_tpu.scheduler.device_manager import DeviceManager

    server, clients = rpc
    service = StateSyncService()
    service.attach(server)
    server.start()

    snap = ClusterSnapshot(capacity=16)
    cfg = ScoringConfig.default().replace(
        usage_thresholds=np.zeros(R, np.int32),
        estimator_defaults=np.zeros(R, np.int32))
    sched = Scheduler(snap, config=cfg, cpu_manager=CPUManager(),
                      device_manager=DeviceManager())
    SolveService(sched).attach(server)
    sync = StateSyncClient(SchedulerBinding(sched))
    client = connect(server, clients, on_push=sync.on_push)
    sync.bootstrap(client)

    cpus = tuple(procfs.CPUInfo(cpu=i, core=i // 2, socket=0, node=i // 4)
                 for i in range(8))
    topo = NodeTopology(
        zones=(NUMAZone("node0", 4_000, 1 << 30, (0, 1, 2, 3)),
               NUMAZone("node1", 4_000, 1 << 30, (4, 5, 6, 7))),
        cpu_topology=cpus)
    service.upsert_node(
        "n1",
        resource_vector({"cpu": 16_000, "memory": 65_536,
                         "kubernetes.io/gpu": 400,
                         "kubernetes.io/gpu-memory": 81_920 * 4}),
        annotations=topo.to_annotations(),
        devices={"gpu": [{"core": 100, "memory": 81_920, "group": 0}
                         for _ in range(4)]})
    wait_until(lambda: sync.rv == service.rv)

    service.add_pod("lsr-1", resource_vector({"cpu": 2_000, "memory": 512}),
                    priority=9_000, qos=int(QoSClass.LSR))
    wait_until(lambda: sync.rv == service.rv)
    result = solve_remote(client)
    assert result["assignments"]["lsr-1"] == "n1"
    assert len(sched.resource_status["lsr-1"]["resource-status"]
               ["cpuset"].split(",")) == 2

    service.add_pod("gpu-1", resource_vector(
        {"cpu": 1_000, "memory": 512, "kubernetes.io/gpu": 100,
         "kubernetes.io/gpu-memory": 8_192}))
    wait_until(lambda: sync.rv == service.rv)
    result = solve_remote(client)
    assert result["assignments"]["gpu-1"] == "n1"
    assert sched.resource_status["gpu-1"]["device-allocated"]["gpu"]


def test_koordlet_device_report_feeds_scheduler_over_wire(rpc, tmp_path):
    """The full device loop: koordlet daemon reports the Device CR, the
    shell converts it to inventory on NODE_UPSERT, the wire-synced
    scheduler allocates real minors to a GPU pod."""
    import os

    from koordinator_tpu.features import KOORDLET_GATES
    from koordinator_tpu.koordlet.daemon import Daemon
    from koordinator_tpu.koordlet.devices import device_infos_to_inventory
    from koordinator_tpu.koordlet.system.config import (
        make_test_config,
    )
    from koordinator_tpu.scheduler.cpu_manager import CPUManager
    from koordinator_tpu.scheduler.device_manager import DeviceManager

    cfg = make_test_config(tmp_path)
    for i in range(2):
        root = os.path.join(cfg.sys_root, "class", "accel", f"accel{i}")
        os.makedirs(root, exist_ok=True)
        for fn, val in (("uuid", f"GPU-{i}"), ("minor", str(i)),
                        ("mem_total", "81920"), ("mem_used", "0"),
                        ("usage_pct", "0"), ("numa_node", "0"),
                        ("health", "1"), ("type", "gpu")):
            with open(os.path.join(root, fn), "w") as f:
                f.write(val)
    os.makedirs(cfg.proc_root, exist_ok=True)
    with open(cfg.proc_path("stat"), "w") as f:
        f.write("cpu  0 0 0 0 0 0 0 0 0 0\n")
    with open(cfg.proc_path("meminfo"), "w") as f:
        f.write("MemTotal: 1024 kB\nMemAvailable: 512 kB\nCached: 0\n")

    server, clients = rpc
    service = StateSyncService()
    service.attach(server)
    server.start()

    # the shell's device_report_fn: Device CR -> inventory -> NODE_UPSERT
    def on_device_report(device):
        service.upsert_node(
            "n0",
            resource_vector({"cpu": 16_000, "memory": 65_536,
                             "kubernetes.io/gpu": 200,
                             "kubernetes.io/gpu-memory": 81_920 * 2}),
            devices=device_infos_to_inventory(list(device.devices)))

    daemon = Daemon(cfg=cfg, clock=lambda: 1000.0,
                    device_report_fn=on_device_report)
    from koordinator_tpu.koordlet.statesinformer import NodeInfo

    daemon.states.set_node(NodeInfo(name="n0", allocatable={}))

    snap = ClusterSnapshot(capacity=16)
    scoring = ScoringConfig.default().replace(
        usage_thresholds=np.zeros(R, np.int32),
        estimator_defaults=np.zeros(R, np.int32))
    sched = Scheduler(snap, config=scoring, cpu_manager=CPUManager(),
                      device_manager=DeviceManager())
    SolveService(sched).attach(server)
    sync = StateSyncClient(SchedulerBinding(sched))
    client = connect(server, clients, on_push=sync.on_push)
    sync.bootstrap(client)

    KOORDLET_GATES.set("Accelerators", True)
    try:
        daemon.tick()          # reports the Device CR through the shell
    finally:
        KOORDLET_GATES.set("Accelerators", False)
    wait_until(lambda: sync.rv == service.rv)

    service.add_pod("gpu-1", resource_vector(
        {"cpu": 1_000, "memory": 512, "kubernetes.io/gpu": 200,
         "kubernetes.io/gpu-memory": 16_384}))
    wait_until(lambda: sync.rv == service.rv)
    result = solve_remote(client)
    assert result["assignments"]["gpu-1"] == "n0"
    minors = [g["minor"] for g in
              sched.resource_status["gpu-1"]["device-allocated"]["gpu"]]
    assert sorted(minors) == [0, 1]   # both probed GPUs allocated


class TestLocalBindings:
    """StateSyncService.attach_binding: the in-process sidecar feed."""

    def test_synchronous_apply_in_rv_order(self):
        applied = []

        class Recorder:
            def node_upsert(self, entry, arrs):
                applied.append(("node", entry["name"]))

            def pod_add(self, entry, arrs):
                applied.append(("pod", entry["name"]))

            def pod_remove(self, name):
                applied.append(("rm", name))

        service = StateSyncService()
        service.attach_binding(Recorder())
        service.upsert_node("n1", resource_vector(cpu=8_000, memory=8_192))
        service.add_pod("p1", resource_vector(cpu=500, memory=512))
        service.remove_pod("p1")
        # applied before each mutation returned, in commit order
        assert applied == [("node", "n1"), ("pod", "p1"), ("rm", "p1")]

    def test_service_stays_live_while_a_binding_apply_blocks(self):
        """The liveness contract: binding applies run OUTSIDE the service
        lock, so a push stuck behind a long solve (the binding blocks on
        scheduler.lock) cannot stall HELLO/snapshot for other peers."""
        gate = threading.Event()
        entered = threading.Event()

        class Stuck:
            def node_upsert(self, entry, arrs):
                entered.set()
                assert gate.wait(10), "test gate never opened"

        service = StateSyncService()
        service.attach_binding(Stuck())
        pusher = threading.Thread(
            target=lambda: service.upsert_node(
                "slow", resource_vector(cpu=1_000, memory=1_024)),
            daemon=True)
        pusher.start()
        assert entered.wait(5), "binding apply never started"
        # the pusher is parked inside the binding; the service must still
        # answer a fresh HELLO (snapshot) without waiting for it
        doc, _ = service._handle_hello({"last_rv": -1, "proto": 3}, {})
        assert doc["rv"] == 1 and len(doc["events"]) == 1
        gate.set()
        pusher.join(5)
        assert not pusher.is_alive()


def test_node_devices_push_registers_inventory(tmp_path):
    """node_devices frames (the device daemon's report loop in wire
    form): a pushed inventory lands in the scheduler's device manager
    through the binding, merges into the stored node doc for bootstrap
    replay, and an unknown node fails the call without touching the
    log."""
    from koordinator_tpu.cmd.binaries import main_koord_scheduler
    from koordinator_tpu.transport.wire import FrameType

    asm = main_koord_scheduler([
        "--node-capacity", "8",
        "--listen-socket", str(tmp_path / "dev.sock"),
        "--disable-leader-election",
    ])
    try:
        asm.state_sync.upsert_node(
            "n-dev", resource_vector(cpu=8_000, memory=8_192))
        client = RpcClient(asm.server.path)
        client.connect()
        try:
            inventory = {"gpu": [{"core": 100, "memory": 1 << 14,
                                  "group": 0}] * 2}
            _, doc, _ = client.call(
                FrameType.STATE_PUSH,
                {"kind": "node_devices", "name": "n-dev",
                 "devices": inventory})
            assert doc["rv"] == 2
            state = asm.component.device_manager.state("gpu")
            assert state is not None
            assert int(np.asarray(state.valid).sum()) == 2
            # the stored node doc carries the inventory for bootstrap
            stored = asm.state_sync.nodes["n-dev"]["doc"]["devices"]
            assert stored == inventory

            with pytest.raises(RpcError, match="unknown node"):
                client.call(FrameType.STATE_PUSH,
                            {"kind": "node_devices", "name": "ghost",
                             "devices": inventory})
            assert asm.state_sync.rv == 2
        finally:
            client.close()
    finally:
        asm.stop()


def test_node_devices_refresh_clears_disappeared_types(tmp_path):
    """A full-inventory refresh must clear types that vanished, or live
    state diverges from what bootstrap replay would build."""
    from koordinator_tpu.cmd.binaries import main_koord_scheduler
    from koordinator_tpu.transport.wire import FrameType

    asm = main_koord_scheduler([
        "--node-capacity", "8",
        "--listen-socket", str(tmp_path / "dev2.sock"),
        "--disable-leader-election",
    ])
    try:
        asm.state_sync.upsert_node(
            "n-dev", resource_vector(cpu=8_000, memory=8_192))
        client = RpcClient(asm.server.path)
        client.connect()
        try:
            client.call(FrameType.STATE_PUSH,
                        {"kind": "node_devices", "name": "n-dev",
                         "devices": {"gpu": [{"core": 100,
                                              "memory": 1 << 14}]}})
            manager = asm.component.device_manager
            assert int(np.asarray(manager.state("gpu").valid).sum()) == 1
            # gpu collector disappears; tpu appears
            client.call(FrameType.STATE_PUSH,
                        {"kind": "node_devices", "name": "n-dev",
                         "devices": {"xpu": [{"core": 100,
                                              "memory": 1 << 14}]}})
            assert int(np.asarray(manager.state("xpu").valid).sum()) == 1
            gpu_state = manager.state("gpu")
            assert gpu_state is None or int(
                np.asarray(gpu_state.valid).sum()) == 0
        finally:
            client.close()
    finally:
        asm.stop()


def test_node_upsert_clears_omitted_device_types(tmp_path):
    """upsert_node REPLACES the stored doc's devices wholesale, so the
    live registration must clear omitted types too — otherwise the
    in-process scheduler keeps allocating devices a bootstrap-replay
    client cannot see (live-vs-replay divergence on the upsert kind)."""
    from koordinator_tpu.cmd.binaries import main_koord_scheduler

    asm = main_koord_scheduler([
        "--node-capacity", "8",
        "--listen-socket", str(tmp_path / "dev3.sock"),
        "--disable-leader-election",
    ])
    try:
        inventory = {"gpu": [{"core": 100, "memory": 1 << 14, "group": 0}]}
        asm.state_sync.upsert_node(
            "n-up", resource_vector(cpu=8_000, memory=8_192),
            devices=inventory)
        manager = asm.component.device_manager
        assert int(np.asarray(manager.state("gpu").valid).sum()) == 1
        # a label-only re-upsert omits devices: stored doc now has {},
        # so live tensors must clear to match what replay would build
        asm.state_sync.upsert_node(
            "n-up", resource_vector(cpu=8_000, memory=8_192),
            labels={"zone": "b"})
        assert asm.state_sync.nodes["n-up"]["doc"]["devices"] == {}
        gpu_state = manager.state("gpu")
        assert gpu_state is None or int(
            np.asarray(gpu_state.valid).sum()) == 0
    finally:
        asm.stop()


def test_reset_clears_fine_grained_registries():
    """Snapshot resync = restart semantics: device tensors and CPU
    topologies must not survive reset(), or types absent from the
    replayed snapshot stay live and allocatable."""
    from koordinator_tpu.ops.numa import CPUTopology
    from koordinator_tpu.scheduler.cpu_manager import CPUManager
    from koordinator_tpu.scheduler.device_manager import DeviceManager
    from koordinator_tpu.scheduler.scheduler import Scheduler
    from koordinator_tpu.scheduler.snapshot import ClusterSnapshot, NodeSpec
    from koordinator_tpu.transport.deltasync import SchedulerBinding

    snap = ClusterSnapshot(capacity=8)
    sched = Scheduler(snap, config=ScoringConfig.default(),
                      cpu_manager=CPUManager(),
                      device_manager=DeviceManager())
    snap.upsert_node(NodeSpec(
        name="n0",
        allocatable=np.asarray(resource_vector(cpu=8_000, memory=8_192)),
        usage=np.zeros(R, np.int32)))
    sched.device_manager.register_node_devices(
        "gpu", "n0", [{"core": 100, "memory": 1 << 14}])
    sched.cpu_manager.register_node(
        "n0", CPUTopology.uniform(sockets=1, numa_per_socket=1,
                                  cores_per_numa=4))
    SchedulerBinding(sched).reset()
    assert sched.device_manager.state("gpu") is None
    assert sched.device_manager.registered_types_for("n0") == set()
    assert sched.cpu_manager.node("n0") is None


def test_direct_api_rejects_malformed_device_inventory():
    """upsert_node / update_node_devices validate inventory shape at the
    DIRECT API too (the wire push validator does not cover in-process
    callers): a non-list type value would commit to the log, skip
    registration on replay, yet count as 'present' for full-inventory
    clearing — silent live-vs-replay divergence."""
    from koordinator_tpu.transport.deltasync import StateSyncService
    from koordinator_tpu.transport.wire import WireSchemaError

    service = StateSyncService()
    with pytest.raises(WireSchemaError, match="must be a list"):
        service.upsert_node("n0", resource_vector(cpu=1_000, memory=1_024),
                            devices={"gpu": "bogus"})
    service.upsert_node("n0", resource_vector(cpu=1_000, memory=1_024))
    with pytest.raises(WireSchemaError, match="must be a list"):
        service.update_node_devices("n0", {"gpu": "bogus"})
    with pytest.raises(WireSchemaError, match="must be an integer"):
        service.update_node_devices(
            "n0", {"gpu": [{"core": "a-hundred"}]})
    # nothing malformed entered the log: rv is still just the upsert
    assert service.rv == 1


def test_node_upsert_clears_stale_cpu_topology(tmp_path):
    """The NRT twin of the device-clearing rule: a re-upsert whose
    annotations no longer carry a cpu-topology must clear the live
    topology — the stored doc was replaced wholesale, so a replayed
    client has no topology either."""
    import json as _json

    from koordinator_tpu.cmd.binaries import main_koord_scheduler

    asm = main_koord_scheduler([
        "--node-capacity", "8",
        "--listen-socket", str(tmp_path / "nrt.sock"),
        "--disable-leader-election",
    ])
    try:
        detail = [{"core": c // 2, "node": 0, "socket": 0, "id": c}
                  for c in range(4)]
        asm.state_sync.upsert_node(
            "n-nrt", resource_vector(cpu=4_000, memory=4_096),
            annotations={"node.koordinator.sh/cpu-topology":
                         _json.dumps({"detail": detail})})
        mgr = asm.component.cpu_manager
        assert mgr.node("n-nrt") is not None
        # label-only re-upsert: no NRT annotation -> topology clears
        asm.state_sync.upsert_node(
            "n-nrt", resource_vector(cpu=4_000, memory=4_096),
            labels={"zone": "b"})
        assert mgr.node("n-nrt") is None
    finally:
        asm.stop()


def test_unchanged_device_heartbeat_does_not_churn_the_log():
    """The koordlet sink re-pushes inventory every interval (heartbeat);
    an UNCHANGED push must not append to the bounded delta log or wake
    watchers — N nodes heartbeating would shrink retention to ~4096/N
    intervals and force slow watchers into full resyncs."""
    from koordinator_tpu.transport.deltasync import StateSyncService

    service = StateSyncService()
    service.upsert_node("n0", resource_vector(cpu=1_000, memory=1_024))
    inventory = {"gpu": [{"core": 100, "memory": 1 << 14, "group": 0}]}
    rv = service.update_node_devices("n0", inventory)
    assert rv == 2
    # identical heartbeat: same rv back, nothing committed
    assert service.update_node_devices("n0", dict(inventory)) == 2
    assert service.rv == 2
    # a real change commits again
    assert service.update_node_devices("n0", {}) == 3


def test_node_remove_clears_fine_grained_registries(tmp_path):
    """NODE_REMOVE takes the node's device tensors and CPU topology with
    it — a bootstrap-replay client has neither, so live state keeping
    them would re-create the divergence the upsert/refresh paths fix."""
    import json as _json

    from koordinator_tpu.cmd.binaries import main_koord_scheduler

    asm = main_koord_scheduler([
        "--node-capacity", "8",
        "--listen-socket", str(tmp_path / "rm.sock"),
        "--disable-leader-election",
    ])
    try:
        detail = [{"core": c, "node": 0, "socket": 0, "id": c}
                  for c in range(2)]
        asm.state_sync.upsert_node(
            "n-rm", resource_vector(cpu=2_000, memory=2_048),
            annotations={"node.koordinator.sh/cpu-topology":
                         _json.dumps({"detail": detail})},
            devices={"gpu": [{"core": 100, "memory": 1 << 14,
                              "group": 0}]})
        dm = asm.component.device_manager
        cm = asm.component.cpu_manager
        assert int(np.asarray(dm.state("gpu").valid).sum()) == 1
        assert cm.node("n-rm") is not None
        asm.state_sync.remove_node("n-rm")
        gpu_state = dm.state("gpu")
        assert gpu_state is None or int(
            np.asarray(gpu_state.valid).sum()) == 0
        assert dm.registered_types_for("n-rm") in (set(), {"gpu"})
        assert cm.node("n-rm") is None
    finally:
        asm.stop()


def test_node_allocatable_push_merges_without_clobbering(rpc):
    """The noderesource controller's wire form: a node_allocatable push
    replaces ONLY the allocatable vector — usage, labels, and the stored
    doc's devices survive — and the merged value rides a later bootstrap
    snapshot.  Unknown node fails the call without touching the log."""
    from koordinator_tpu.api import extension as ext
    from koordinator_tpu.transport.channel import RpcRemoteError
    from koordinator_tpu.transport.wire import FrameType

    server, clients = rpc
    service = StateSyncService()
    service.attach(server)
    server.start()
    service.upsert_node(
        "n1", resource_vector(cpu=16_000, memory=65_536),
        usage=resource_vector(cpu=4_000, memory=8_192),
        labels={"zone": "a"},
        devices={"gpu": [{"core": 100, "memory": 1 << 14, "group": 0}]})

    sched = mk_scheduler()
    sync = StateSyncClient(SchedulerBinding(sched))
    client = connect(server, clients, on_push=sync.on_push)
    sync.bootstrap(client)

    new_alloc = resource_vector({
        "cpu": 16_000, "memory": 65_536,
        ext.RESOURCE_BATCH_CPU: 9_000, ext.RESOURCE_BATCH_MEMORY: 30_000})
    _, doc, _ = client.call(
        FrameType.STATE_PUSH,
        {"kind": "node_allocatable", "name": "n1"},
        {"allocatable": np.asarray(new_alloc, np.int32)})
    assert doc["rv"] == service.rv
    wait_until(lambda: sync.rv == service.rv)

    spec = sched.snapshot.node_specs["n1"]
    from koordinator_tpu.api.resources import ResourceDim
    assert spec.allocatable[ResourceDim.BATCH_CPU] == 9_000
    assert spec.usage[ResourceDim.CPU] == 4_000       # usage untouched
    assert spec.labels == {"zone": "a"}
    stored = service.nodes["n1"]
    assert stored["doc"]["devices"]["gpu"]            # inventory survives
    assert int(stored["arrays"]["allocatable"][ResourceDim.BATCH_CPU]) \
        == 9_000

    # a fresh bootstrapper replays the MERGED allocatable
    sched2 = mk_scheduler()
    sync2 = StateSyncClient(SchedulerBinding(sched2))
    client2 = connect(server, clients, on_push=sync2.on_push)
    sync2.bootstrap(client2)
    assert sched2.snapshot.node_specs["n1"].allocatable[
        ResourceDim.BATCH_CPU] == 9_000

    with pytest.raises(RpcRemoteError, match="unknown node"):
        client.call(FrameType.STATE_PUSH,
                    {"kind": "node_allocatable", "name": "ghost"},
                    {"allocatable": np.asarray(new_alloc, np.int32)})


def test_conn_close_with_full_queue_does_not_leak_sender_thread():
    """_Conn.close vs a momentarily-full queue: the sender can drain the
    whole backlog between close()'s failed poison put and its direct
    socket shutdown, then block forever on queue.get() with no poison
    coming.  close() must retry the poison after the shutdown so the
    sender thread always exits."""
    import queue as _queue

    from koordinator_tpu.transport.channel import _Conn
    from koordinator_tpu.transport.wire import (
        Frame,
        FrameType,
        encode_payload,
    )

    drained = threading.Event()
    in_send = threading.Event()

    class FakeSock:
        """sendall blocks until released; shutdown (called from close's
        Full branch) WAITS for the sender to drain the backlog — the
        exact interleaving that leaked the thread."""

        def __init__(self):
            self.release = threading.Event()

        def sendall(self, data):
            in_send.set()
            self.release.wait(5)

        def shutdown(self, how):
            # simulate the race window: by the time the shutdown lands,
            # the sender has drained everything and is parked in get()
            self.release.set()
            assert drained.wait(5), "sender never drained the backlog"

    conn = _Conn.__new__(_Conn)
    conn.sock = FakeSock()
    conn.faults = None
    conn._held = None
    conn.queue = _queue.Queue(4)
    conn.alive = True
    conn.dropped = 0

    orig_get = conn.queue.get

    def tracking_get(*a, **kw):
        if conn.queue.empty():
            drained.set()
        return orig_get(*a, **kw)

    conn.queue.get = tracking_get
    frame = Frame(FrameType.DELTA, 0, encode_payload({"x": 1}))
    # sender holds one frame inside the blocked sendall...
    conn.queue.put_nowait((frame, 0.0))
    sender = threading.Thread(target=conn._drain, daemon=True)
    conn._sender = sender
    sender.start()
    assert in_send.wait(5)
    # ...while the queue refills to capacity: close() sees Full
    for _ in range(4):
        conn.queue.put_nowait((frame, 0.0))

    conn.close()          # Full -> shutdown (sender drains) -> poison retry
    sender.join(5)
    assert not sender.is_alive(), \
        "sender thread leaked: blocked on queue.get() with no poison"


# -- a live DELTA frame is built by its connection's sender, from the log ----


def _count_packs(monkeypatch):
    """Count the calls of the two event codecs (module globals, so the
    senders' frames and the HELLO handler both go through the wrappers)."""
    from koordinator_tpu.transport import deltasync

    calls = {"v2": 0, "v1": 0}
    pack_v2, pack_v1 = deltasync._pack_events_v2, deltasync._pack_events

    def counted_v2(events):
        calls["v2"] += 1
        return pack_v2(events)

    def counted_v1(events):
        calls["v1"] += 1
        return pack_v1(events)

    monkeypatch.setattr(deltasync, "_pack_events_v2", counted_v2)
    monkeypatch.setattr(deltasync, "_pack_events", counted_v1)
    return calls, pack_v2, pack_v1


def _frame_counts():
    from koordinator_tpu import metrics

    return {labels["outcome"]: int(value)
            for labels, value in metrics.sync_delta_frames_total.items()}


def _mutate(service, mix: str, n: int) -> None:
    service.upsert_node("n0", resource_vector(cpu=64_000, memory=65_536))
    for i in range(n - 1):
        kind = mix if mix != "mixed" else ("pods", "usage", "removes")[i % 3]
        if kind == "pods":
            service.add_pod(f"p{i}", resource_vector(cpu=100 + i, memory=64),
                            priority=i % 3, labels={"app": f"a{i % 2}"})
        elif kind == "usage":
            service.update_node_usage(
                "n0", resource_vector(cpu=10 * i, memory=i))
        else:
            service.remove_pod(f"p{i}")


@pytest.mark.parametrize("mix", ["pods", "usage", "mixed"])
def test_event_with_no_watcher_builds_no_delta_frame(rpc, monkeypatch, mix):
    """A server is attached and listening but nobody is connected: N
    mutations pack nothing, the log holds all N, and a watcher that says
    HELLO afterwards is served every one of them from the log."""
    server, clients = rpc
    service = StateSyncService()
    service.attach(server)
    server.start()
    calls, pack_v2, _ = _count_packs(monkeypatch)
    n = 40
    _mutate(service, mix, n)
    assert calls == {"v2": 0, "v1": 0}
    assert _frame_counts() == {"no_recipient": n}
    assert service.rv == n
    assert [rv for rv, _, _ in service.log.since(0)] == list(range(1, n + 1))

    client = connect(server, clients)
    ftype, doc, arrays = client.call(
        FrameType.HELLO, {"last_rv": 0, "proto": PROTOCOL_VERSION})
    assert ftype is FrameType.DELTA
    want_doc, want_arrays = pack_v2(service.log.since(0))
    assert doc == dict(want_doc, rv=n, proto=PROTOCOL_VERSION,
                       instance=service.instance)
    assert sorted(arrays) == sorted(want_arrays)
    for key, want in want_arrays.items():
        assert arrays[key].dtype == want.dtype
        assert np.array_equal(arrays[key], want)



def _sent_counts() -> tuple[int, int]:
    """(live DELTA frames handed to a socket, events they carried)."""
    from koordinator_tpu import metrics

    return (int(metrics.sync_delta_frames_sent_total.value()),
            int(metrics.sync_delta_events_sent_total.value()))


def _decoded(payloads) -> list[tuple[dict, dict]]:
    """The events of a peer's DELTA payloads in arrival order, each as
    (entry without its row manifest, its arrays), whichever codec."""
    from koordinator_tpu.transport.deltasync import (
        _decode_events,
        _unpack_event_arrays,
    )

    out = []
    for payload in payloads:
        doc, arrays = decode_payload(payload)
        for entry in _decode_events(doc, arrays):
            arrs = _unpack_event_arrays(entry, arrays)
            out.append(({k: v for k, v in entry.items()
                         if not k.startswith("__row_")}, arrs))
    return out


def _assert_same_events(got, logged) -> None:
    assert [e["rv"] for e, _ in got] == [rv for rv, _, _ in logged]
    for (entry, arrs), (rv, event, arrays) in zip(got, logged):
        assert entry == dict(event, rv=rv)
        assert sorted(arrs) == sorted(arrays)
        for key, want in arrays.items():
            assert np.array_equal(arrs[key], want)


def _dial_peers(server, clients, peers):
    """One raw client per entry of ``peers`` ("v4" / "v3" say HELLO at
    that protocol, "no_hello" and "dead" never do; a "dead" one is
    marked not alive while still listed).  Returns each one's received
    push frames."""
    got: dict[int, list] = {}
    for i, peer in enumerate(peers):
        got[i] = []
        client = connect(server, clients,
                         on_push=lambda frame, i=i: got[i].append(frame))
        if peer in ("v4", "v3"):
            client.call(FrameType.HELLO, {
                "last_rv": -1, "proto": (PROTOCOL_VERSION if peer == "v4"
                                         else MIN_PROTOCOL_VERSION)})
        # listed before the next peer dials, so the list is in dial order
        wait_until(lambda: len(server._conns) == i + 1)
    for conn, peer in zip(list(server._conns), peers):
        if peer == "dead":
            conn.alive = False
    return got


@pytest.mark.parametrize("peers, forms, outcome", [
    (("v4", "v3"), {"v2", "v1"}, "built"),
    (("v4",), {"v2"}, "built"),
    (("v3",), {"v1"}, "built"),
    (("no_hello",), {"v1"}, "built"),
    (("v4", "v4", "v3", "v3"), {"v2", "v1"}, "built"),
    (("dead",), set(), "no_recipient"),
    (("dead", "v4"), {"v2"}, "built"),
], ids=lambda v: "+".join(v) if isinstance(v, tuple) else None)
def test_connected_peers_get_every_event_once_in_their_own_form(
        rpc, monkeypatch, peers, forms, outcome):
    """Every recipient connected at an event receives it exactly once,
    in rv order, as what was committed: in columnar frames at proto >=
    4, in v1 frames below it (a peer that never said HELLO included),
    in no more frames than events.  Only the forms some live peer speaks
    are ever packed; a connection that is still listed but no longer
    alive receives and builds nothing.  Both counters of what was sent
    agree with what arrived."""
    server, clients = rpc
    service = StateSyncService()
    service.attach(server)
    server.start()
    got = _dial_peers(server, clients, peers)
    calls, _, _ = _count_packs(monkeypatch)
    n = 12
    _mutate(service, "mixed", n)
    logged = service.log.since(0)
    live = [i for i, peer in enumerate(peers) if peer != "dead"]
    for i in live:
        wait_until(lambda: len(_decoded(f.payload for f in got[i])) >= n)
    assert _frame_counts() == {outcome: n}
    assert {form for form, k in calls.items() if k} == forms
    for i, peer in enumerate(peers):
        if peer == "dead":
            assert got[i] == []
            continue
        assert all(f.type is FrameType.DELTA and f.request_id == 0
                   for f in got[i])
        assert 1 <= len(got[i]) <= n
        marker = b"events_v2" if peer == "v4" else b'"events"'
        assert all(marker in f.payload for f in got[i])
        _assert_same_events(_decoded(f.payload for f in got[i]), logged)
    assert _sent_counts() == (sum(len(got[i]) for i in live), n * len(live))
    # a ready single-event frame is packed once per wire form, a run once
    # per connection
    assert sum(calls.values()) <= _sent_counts()[0]


def test_event_of_a_kind_without_a_code_reaches_every_peer_as_v1(rpc):
    """_pack_events_v2 answers None for a kind it has no code for: v4
    and v3 peers alike then get the v1 frame, still built on demand."""
    server, clients = rpc
    service = StateSyncService()
    service.attach(server)
    server.start()
    event = {"kind": "mystery_kind", "name": "x", "payload": {"a": 1}}
    arrays = {"vec": np.arange(4, dtype=np.int32)}
    service._store_and_commit(lambda: None, dict(event), arrays)
    assert _frame_counts() == {"no_recipient": 1}
    assert _sent_counts() == (0, 0)

    got: dict[int, list[bytes]] = {0: [], 1: []}
    for i, proto in enumerate((PROTOCOL_VERSION, MIN_PROTOCOL_VERSION)):
        client = connect(
            server, clients,
            on_push=lambda frame, i=i: got[i].append(frame.payload))
        client.call(FrameType.HELLO, {"last_rv": 1, "proto": proto})
    service._store_and_commit(lambda: None, dict(event), arrays)
    want = encode_payload(*_pack_events(service.log.since(1)))
    assert b'"events"' in want and b"events_v2" not in want
    for i in got:
        wait_until(lambda: len(got[i]) == 1)
        assert got[i] == [want]
    assert _frame_counts() == {"no_recipient": 1, "built": 1}
    assert _sent_counts() == (2, 2)


class MirrorBinding:
    """Keeps what the events say, by name: enough to compare a watcher's
    view with the service's stored state, and to see an event applied
    twice (``applies``) or a snapshot taken (``resets``)."""

    def __init__(self):
        self.resets = 0
        self.reset()
        self.resets = 0

    def reset(self):
        self.resets += 1
        self.nodes: dict[str, list[int]] = {}
        self.pods: dict[str, list[int]] = {}
        self.applies: dict[str, int] = {}

    def node_upsert(self, entry, arrs):
        self.nodes[entry["name"]] = arrs["usage"].tolist()

    def node_usage(self, entry, arrs):
        self.nodes[entry["name"]] = arrs["usage"].tolist()

    def pod_add(self, entry, arrs):
        name = entry["name"]
        self.pods[name] = arrs["requests"].tolist()
        self.applies[name] = self.applies.get(name, 0) + 1

    def pod_remove(self, name):
        self.pods.pop(name, None)

    def equals(self, service) -> bool:
        return (self.nodes == {k: v["arrays"]["usage"].tolist()
                               for k, v in service.nodes.items()}
                and self.pods == {k: v["arrays"]["requests"].tolist()
                                  for k, v in service.pods.items()})


def _watch(server, clients, service, binding=None, **client_kw):
    """A bootstrapped StateSyncClient on a fresh connection."""
    sync = StateSyncClient(binding or MirrorBinding())
    client = connect(server, clients, on_push=sync.on_push, **client_kw)
    sync.bind_client(client)
    sync.bootstrap(client)
    return sync, client


def test_burst_behind_a_held_sender_arrives_in_runs(rpc):
    """(a) N events commit while the watcher's sender cannot read the
    log (the committer holds the service's lock through the burst, as a
    tight producer holds the interpreter): the first goes out ready-made
    while the connection is still idle, the connection's queue then
    takes ONE notice, and what arrives is far fewer frames than events,
    in rv order, every event applied once."""
    server, clients = rpc
    service = StateSyncService()
    service.attach(server)
    server.start()
    service.upsert_node("n0", resource_vector(cpu=64_000, memory=65_536))
    frames: list = []
    sync = StateSyncClient(MirrorBinding())
    client = connect(server, clients, on_push=lambda f: (
        frames.append(f), sync.on_push(f)))
    sync.bootstrap(client)
    wait_until(lambda: len(server._conns) == 1)
    conn = server._conns[0]
    applied0, n = sync.applied, 500
    with service._lock:
        for i in range(n):
            service.update_node_usage(
                "n0", resource_vector(cpu=10 + i, memory=i))
        assert conn.notified and conn.queue.qsize() <= 2
        assert conn.cursor < service.rv
    wait_until(lambda: sync.rv == service.rv)
    assert 1 <= len(frames) <= n // 10
    assert [e["rv"] for e, _ in _decoded(f.payload for f in frames)] == \
        list(range(service.rv - n + 1, service.rv + 1))
    assert sync.applied - applied0 == n and sync.gaps == 0
    assert sync.skipped == 0
    assert _sent_counts() == (len(frames), n)
    assert _frame_counts() == {"no_recipient": 1, "built": n}
    assert conn.cursor == service.rv and not conn.notified
    assert sync.binding.equals(service)


def test_a_v4_and_a_v3_peer_each_get_the_run_in_their_own_form(rpc):
    """(b) side by side behind one burst: columnar frames for the v4
    peer, v1 frames for the v3 peer, the same events in both, each far
    fewer frames than events."""
    server, clients = rpc
    service = StateSyncService()
    service.attach(server)
    server.start()
    got = _dial_peers(server, clients, ("v4", "v3"))
    n = 60
    with service._lock:
        _mutate(service, "mixed", n)
    logged = service.log.since(0)
    for i in got:
        wait_until(lambda: len(_decoded(f.payload for f in got[i])) == n)
        _assert_same_events(_decoded(f.payload for f in got[i]), logged)
        assert len(got[i]) <= n // 10
    assert all(b"events_v2" in f.payload for f in got[0])
    assert all(b'"events"' in f.payload and b"events_v2" not in f.payload
               for f in got[1])
    assert _sent_counts() == (len(got[0]) + len(got[1]), 2 * n)


def test_a_pushs_delta_precedes_its_reply_on_the_pushers_connection(rpc):
    """(c) the notice stands in the same queue as the replies: when a
    STATE_PUSH call returns, the DELTA that carries its event has already
    been delivered to the pusher's own watch."""
    server, clients = rpc
    service = StateSyncService()
    service.attach(server)
    server.start()
    service.upsert_node("n0", resource_vector(cpu=64_000, memory=65_536))
    sync, client = _watch(server, clients, service)
    other, _ = _watch(server, clients, service)
    for i in range(50):
        _, doc, _ = client.call(
            FrameType.STATE_PUSH, {"kind": "node_usage", "name": "n0"},
            {"usage": np.asarray(resource_vector(cpu=i, memory=i),
                                 np.int32)})
        assert sync.rv >= doc["rv"], f"push {i}: reply overtook its DELTA"
    wait_until(lambda: other.rv == service.rv)
    assert sync.gaps == other.gaps == 0
    assert sync.binding.equals(service) and other.binding.equals(service)


def test_hello_racing_live_commits_neither_loses_nor_doubles(rpc):
    """(d) watchers dial, bootstrap and re-bootstrap while a committer
    never stops: whatever each HELLO served, the live stream resumes
    right after it, so every pod is applied exactly once since the
    watcher's last snapshot and none is missing."""
    server, clients = rpc
    service = StateSyncService()
    service.attach(server)
    server.start()
    service.upsert_node("n0", resource_vector(cpu=64_000, memory=65_536))
    stop = threading.Event()
    total = [0]

    def commit():
        while not stop.is_set() and total[0] < 3_000:
            service.add_pod(f"p{total[0]}",
                            resource_vector(cpu=1 + total[0], memory=1))
            total[0] += 1
            if total[0] % 7 == 0:
                time.sleep(0.0005)

    committer = threading.Thread(target=commit, daemon=True)
    committer.start()
    watchers = []
    try:
        for _ in range(4):
            sync, client = _watch(server, clients, service)
            watchers.append(sync)
            for _ in range(3):
                time.sleep(0.01)
                sync.bootstrap(client)   # a re-HELLO on the live stream
    finally:
        stop.set()
        committer.join(10)
    assert total[0] > 0
    for sync in watchers:
        wait_until(lambda: sync.rv == service.rv)
        assert sync.gaps == 0
        assert sync.binding.equals(service)
        assert set(sync.binding.applies.values()) == {1}


def test_push_before_the_first_bootstrap_cannot_skip_the_snapshot(rpc):
    """(d) a connection is a recipient from the moment it is listed: an
    event committed between a watcher's dial and its first HELLO reaches
    it as a live frame.  A watcher that has never synced drops it (the
    HELLO's snapshot holds it) instead of taking its rv for its own."""
    server, clients = rpc
    service = StateSyncService()
    service.attach(server)
    server.start()
    _mutate(service, "pods", 20)
    frames: list = []
    sync = StateSyncClient(MirrorBinding())
    client = connect(server, clients, on_push=lambda f: (
        sync.on_push(f), frames.append(f)))
    wait_until(lambda: len(server._conns) == 1)
    service.add_pod("between", resource_vector(cpu=3, memory=3))
    wait_until(lambda: len(frames) == 1)
    assert sync.rv == -1 and sync.applied == 0
    sync.bootstrap(client)
    assert sync.rv == service.rv and sync.binding.equals(service)
    assert set(sync.binding.applies.values()) == {1}


def test_watcher_behind_the_retained_log_is_poisoned_and_resnapshots(rpc):
    """(e) what "too far behind" means now: the watcher's cursor has left
    the log's retained window.  Its sender finds ResyncRequired, poisons
    the connection, and the watcher's next HELLO is served the snapshot
    (the HELLO's own rule, untouched)."""
    server, clients = rpc
    service = StateSyncService(retention=64)
    service.attach(server)
    server.start()
    service.upsert_node("n0", resource_vector(cpu=64_000, memory=65_536))
    sync, client = _watch(server, clients, service)
    wait_until(lambda: len(server._conns) == 1)
    conn = server._conns[0]
    with service._lock:
        for i in range(130):    # twice what the log keeps
            service.update_node_usage(
                "n0", resource_vector(cpu=10 + i, memory=i))
    wait_until(lambda: not client.connected)
    assert not conn.alive and conn.dropped == 1
    assert _sent_counts()[1] < 64       # the run was never built
    resets = sync.binding.resets     # the first bootstrap's snapshot
    assert sync.rv < service.rv
    service.update_node_usage("n0", resource_vector(cpu=7, memory=7))
    client = connect(server, clients, on_push=sync.on_push)
    sync.bootstrap(client)
    assert sync.binding.resets == resets + 1, "came back by DELTA"
    assert sync.rv == service.rv and sync.binding.equals(service)


def test_burst_of_a_whole_wave_does_not_poison_a_reader_that_keeps_up(rpc):
    """(e) 10,240 reports in one tight loop, two and a half times what
    the log retains and what the send queue holds: the sender gets its
    turns of the interpreter, takes a run each time, and the watcher
    that reads them is never poisoned."""
    server, clients = rpc
    service = StateSyncService()
    service.attach(server)
    server.start()
    names = [f"n{i}" for i in range(64)]
    for name in names:
        service.upsert_node(name, resource_vector(cpu=64_000, memory=65_536))
    sync, client = _watch(server, clients, service)
    applied0, n = sync.applied, 10_240
    for i in range(n):
        service.update_node_usage(
            names[i % 64], resource_vector(cpu=1 + i, memory=1 + i % 999))
    wait_until(lambda: sync.rv == service.rv, timeout=60.0)
    assert client.connected, "the burst poisoned the connection"
    assert sync.applied - applied0 == n and sync.gaps == 0
    frames, events = _sent_counts()
    assert events == n and frames < n
    assert sync.binding.resets == 1 and sync.binding.equals(service)


class ScriptedFaults:
    """The server-side seam of the fault injector, scripted: the k-th
    push frame gets ``actions[k]``, everything else goes out clean."""

    def __init__(self, actions):
        self.actions = list(actions)

    def on_read(self):
        pass

    def outbound_action(self, is_push):
        if is_push and self.actions:
            return self.actions.pop(0)
        return None


@pytest.mark.parametrize("action, gaps", [
    ("drop", 1), ("reorder", 1), ("duplicate", 0)])
def test_fault_on_a_many_event_frame_is_caught_and_repaired(tmp_path, action,
                                                            gaps):
    """(f) a frame that carries a whole run is lost, doubled or overtaken
    like any other: a lost or overtaken run shows as an rv gap at the next
    frame, the watcher severs and its re-HELLO takes the snapshot; a
    doubled run is dropped event by event by the rv guard.  Either way
    the watcher ends with the service's state."""
    _fault_on_a_many_event_frame(tmp_path, action, gaps)


def _fault_on_a_many_event_frame(tmp_path, action, gaps):
    server = RpcServer(str(tmp_path / "f.sock"),
                       faults=ScriptedFaults([action]))
    clients: list = []
    try:
        service = StateSyncService()
        service.attach(server)
        server.start()
        service.upsert_node("n0", resource_vector(cpu=64_000, memory=65_536))
        sync, client = _watch(server, clients, service)
        wait_until(lambda: len(server._conns) == 1)
        conn = server._conns[0]
        n = 40
        with service._lock:
            conn.idle = lambda: False         # behind from the first event
            _mutate(service, "mixed", n)      # one frame of n: the fault's
            del conn.idle
        if action == "duplicate":
            wait_until(lambda: sync.skipped == n)
        else:
            # the run is gone (drop) or held back (reorder) until the
            # next frame goes out
            time.sleep(0.05)
            assert sync.rv == 1
        service.add_pod("after", resource_vector(cpu=5, memory=5))
        if gaps:
            wait_until(lambda: not client.connected)
            assert sync.needs_resync
            client = connect(server, clients, on_push=sync.on_push)
            sync.bootstrap(client)
            assert sync.binding.resets == 2     # the first bootstrap's too
        wait_until(lambda: sync.rv == service.rv)
        assert sync.gaps == gaps
        assert sync.binding.equals(service)
        assert _sent_counts() == (2, n + 1)
    finally:
        for c in clients:
            c.close()
        server.stop()


# -- the time work waited in a connection's queues (ISSUE 34) ----------------


def _waits_since(t0: float) -> dict:
    """The recorder's ``waits`` map over [t0, now]."""
    from koordinator_tpu import timeline

    doc = timeline.RECORDER.finish_cycle(1, t0, time.perf_counter(),
                                         publish=False)
    return doc["waits"]


def _held_echo(server):
    """An echo handler that holds the dispatch worker for 50 ms where
    the request says so; ``entered`` is set as it starts to."""
    entered = threading.Event()

    def echo(doc, arrays):
        if doc.get("hold"):
            entered.set()
            time.sleep(0.05)
        return {"ok": True}, None

    server.register(FrameType.SOLVE_REQUEST, echo)
    return entered


def test_a_held_handler_makes_the_next_frame_wait_in_the_inbox(rpc):
    """The reader stays eager behind a busy handler: the second frame is
    read, stamped and queued while the first one's handler runs, and its
    ``rpc.inbox.*`` wait is the time it stood there."""
    server, clients = rpc
    entered = _held_echo(server)
    server.start()
    client = connect(server, clients)
    t0 = time.perf_counter()
    first = threading.Thread(
        target=client.call, args=(FrameType.SOLVE_REQUEST, {"hold": True}))
    first.start()
    assert entered.wait(5)
    client.call(FrameType.SOLVE_REQUEST, {})
    first.join(5)
    waits = _waits_since(t0)
    inbox = waits["rpc.inbox.SOLVE_REQUEST"]
    assert inbox["n"] == 2
    assert inbox["max_s"] >= 0.040
    assert inbox["wait_s"] >= inbox["max_s"]
    # the two replies went through the outbox unhindered
    assert waits["rpc.outbox.SOLVE_RESPONSE"]["n"] == 2
    assert waits["rpc.outbox.SOLVE_RESPONSE"]["max_s"] < 0.040


def test_a_reply_behind_a_held_sender_waits_in_the_outbox(rpc):
    """The sender thread is held on a push notice: the reply queued
    after it keeps its place in the FIFO and its ``rpc.outbox.*`` wait
    is the time the sender took to reach it."""
    server, clients = rpc
    _held_echo(server)
    server.start()
    client = connect(server, clients)
    wait_until(lambda: len(server._conns) == 1)
    conn = server._conns[0]
    gate = threading.Event()
    t0 = time.perf_counter()
    conn.send(lambda c: gate.wait(5) and None)
    threading.Timer(0.05, gate.set).start()
    client.call(FrameType.SOLVE_REQUEST, {})
    waits = _waits_since(t0)
    assert waits["rpc.outbox.SOLVE_RESPONSE"]["n"] == 1
    assert waits["rpc.outbox.SOLVE_RESPONSE"]["wait_s"] >= 0.040
    # the notice itself waited for nobody, and yielded no frame
    assert waits["rpc.outbox.DELTA"]["n"] == 1
    assert waits["rpc.outbox.DELTA"]["max_s"] < 0.040
    assert waits["rpc.inbox.SOLVE_REQUEST"]["max_s"] < 0.040


def test_burst_behind_a_held_sender_shows_its_lag(rpc):
    """A burst commits while the watcher's sender cannot read the log:
    the notice's wait from the commit that queued it to the sender
    reaching it is ``rpc.outbox.DELTA``, and the gauge says how long the
    run was that the sender then took (last, and the peak so far)."""
    from koordinator_tpu import metrics

    server, clients = rpc
    service = StateSyncService()
    service.attach(server)
    server.start()
    service.upsert_node("n0", resource_vector(cpu=64_000, memory=65_536))
    frames: list = []
    sync = StateSyncClient(MirrorBinding())
    client = connect(server, clients, on_push=lambda f: (
        frames.append(f), sync.on_push(f)))
    sync.bootstrap(client)
    wait_until(lambda: len(server._conns) == 1)
    gauge = metrics.sync_watch_cursor_lag_events
    assert gauge.value({"quantity": "peak"}) == 0
    t0, n = time.perf_counter(), 500
    with service._lock:
        for i in range(n):
            service.update_node_usage(
                "n0", resource_vector(cpu=10 + i, memory=i))
    wait_until(lambda: sync.rv == service.rv)
    wait_until(lambda: server._conns[0].idle())
    runs = [len(_decoded([f.payload])) for f in frames]
    assert sum(runs) == n and runs[0] == 1
    assert gauge.value({"quantity": "last"}) == runs[-1]
    assert gauge.value({"quantity": "peak"}) == max(runs) >= n // 10
    delta = _waits_since(t0)["rpc.outbox.DELTA"]
    # one observation per item the sender took: the ready first frame
    # and a notice per run (a notice that found nothing new counts too)
    # (the sender's wait for the service's lock, once it has the notice,
    # is sync.frame's time, not the outbox's)
    assert delta["n"] >= len(frames)
    assert delta["wait_s"] >= delta["max_s"] > 0.0
    # a later, shorter run moves last and leaves the peak
    with service._lock:
        for i in range(3):
            service.update_node_usage(
                "n0", resource_vector(cpu=900 + i, memory=i))
    wait_until(lambda: sync.rv == service.rv)
    assert gauge.value({"quantity": "last"}) <= 3
    assert gauge.value({"quantity": "peak"}) == max(runs)


@pytest.mark.parametrize("action, gaps", [
    ("drop", 1), ("reorder", 1), ("duplicate", 0)])
def test_a_faulted_frame_loses_nothing_to_the_stamp(tmp_path, action, gaps):
    """The stamp rides the queue item, not the frame: under each fault
    the scenario of (f) ends as it ends without the recorder looking
    (the same frames, the same repair), and every DELTA item the senders
    took was observed once, whatever then happened to its bytes."""
    t0 = time.perf_counter()
    _fault_on_a_many_event_frame(tmp_path, action, gaps)
    waits = _waits_since(t0)
    # the run's notice and the ready frame of the event after it
    assert waits["rpc.outbox.DELTA"]["n"] == _sent_counts()[0] == 2
    # every HELLO was answered through the outbox as well
    hellos = waits["rpc.inbox.HELLO"]["n"]
    assert hellos == 1 + gaps
    assert sum(row["n"] for name, row in waits.items()
               if name in ("rpc.outbox.SNAPSHOT", "rpc.outbox.DELTA",
                           "rpc.outbox.ACK")) == 2 + hellos


@pytest.mark.parametrize("enabled", [False, True])
def test_no_stamp_is_taken_with_the_recorder_off(rpc, monkeypatch, enabled):
    """``KOORD_TIMELINE=0``: the channel reads no clock for the waits
    and stores nothing; on, a request costs three reads (arrival, reply
    queued, reply taken) and a pushed event two."""
    from koordinator_tpu import timeline
    from koordinator_tpu.transport import channel

    reads = []

    def counted():
        reads.append(1)
        return time.perf_counter()

    server, clients = rpc
    service = StateSyncService()
    service.attach(server)
    server.start()
    was = timeline.RECORDER.enabled
    timeline.RECORDER.set_enabled(enabled)
    try:
        sync, client = _watch(server, clients, service)
        wait_until(lambda: len(server._conns) == 1
                   and server._conns[0].idle())
        monkeypatch.setattr(channel, "_perf_counter", counted)
        for i in range(5):
            client.call(FrameType.STATE_PUSH,
                        {"kind": "pod_add", "name": f"p{i}", "priority": 1},
                        {"requests": resource_vector(cpu=100, memory=64)})
        wait_until(lambda: sync.rv == service.rv)
        wait_until(lambda: server._conns[0].idle())
        stored = len(timeline.RECORDER._waits)
    finally:
        timeline.RECORDER.set_enabled(was)
    if enabled:
        # 5 requests x 3, and per request its event's echo: 1 or 2 items
        assert 5 * 5 <= len(reads) <= 5 * 7
        assert stored >= 5 * 3
    else:
        assert reads == [] and stored == 0
    assert sync.binding.pods.keys() == {f"p{i}" for i in range(5)}


class _CountingDeque(collections.deque):
    """Counts the entries an iteration from either end hands out."""

    touched = 0

    def __iter__(self):
        for item in super().__iter__():
            self.touched += 1
            yield item

    def __reversed__(self):
        for item in super().__reversed__():
            self.touched += 1
            yield item


@pytest.mark.parametrize("cursor, want", [
    (4_000, list(range(4_001, 5_001))),     # the whole retained window
    (4_999, [5_000]),                       # one behind
    (4_990, list(range(4_991, 5_001))),
    (5_000, []),                            # caught up: an empty tail
    (3_999, None),                          # before the window
], ids=["full_log", "one_behind", "ten_behind", "empty_tail",
        "before_window"])
def test_delta_log_since_reads_only_the_tail_it_returns(cursor, want):
    """(g) the answers are what the scan of the whole log gave, for the
    price of the entries returned (and the one that ends the walk)."""
    log = DeltaLog(retention=1_000)
    for rv in range(1, 5_001):
        log.append(rv, {"kind": "e", "name": str(rv)}, {})
    log._events = _CountingDeque(log._events)
    assert log.oldest_rv() == 4_001
    if want is None:
        with pytest.raises(ResyncRequired):
            log.since(cursor)
        assert log._events.touched == 0
        return
    scanned = [(v, e, a) for v, e, a in list(log._events) if v > cursor]
    log._events.touched = 0
    got = log.since(cursor)
    assert got == scanned and [v for v, _, _ in got] == want
    assert log._events.touched <= len(want) + 1


@pytest.mark.parametrize("sender_is", ["in_sendall", "reading_the_log"])
def test_conn_close_with_a_notice_outstanding_leaks_no_sender(rpc, sender_is):
    """(h) a connection is closed while its DELTA notice is still to be
    served: queued behind a frame the socket will not take, or already
    taken with the sender waiting for the log.  The sender finishes the
    notice and leaves on the poison."""
    server, clients = rpc
    service = StateSyncService()
    service.attach(server)
    server.start()
    service.upsert_node("n0", resource_vector(cpu=64_000, memory=65_536))
    got: list = []
    client = connect(server, clients, on_push=got.append)
    wait_until(lambda: len(server._conns) == 1)
    conn = server._conns[0]
    usage = resource_vector(cpu=1, memory=1)
    if sender_is == "in_sendall":
        release = threading.Event()
        real_send_one = conn._send_one

        def stuck_send_one(frame):
            release.wait(5)
            return real_send_one(frame)

        conn._send_one = stuck_send_one
        service.update_node_usage("n0", usage)     # taken, stuck in send
        wait_until(conn.idle)
        service.update_node_usage("n0", usage)     # a ready frame waits
        service.update_node_usage("n0", usage)     # and a notice behind it
        assert conn.notified and conn.queue.qsize() == 2
        conn.close()
        release.set()
    else:
        with service._lock:
            conn.idle = lambda: False              # behind: a notice
            service.update_node_usage("n0", usage)
            del conn.idle
            wait_until(conn.idle)                  # the sender has it
            conn.close()
    conn._sender.join(5)
    assert not conn._sender.is_alive(), "sender thread leaked"
    wait_until(lambda: not client.connected)
    # what was built before the poison still left, in order
    assert [e["rv"] for e, _ in _decoded(f.payload for f in got)] == \
        list(range(2, service.rv + 1))


@pytest.mark.parametrize("attached", [False, True],
                         ids=["no_server", "server_no_watcher"])
def test_backlog_peak_after_a_burst_from_two_threads(rpc, attached):
    """The binding-backlog watermark is what it was: one pusher parked
    inside a binding apply, two more threads each commit an event behind
    it (depth 1, then 2) and wait for the binding lock; once the gate
    opens everything applies in rv order, the live gauge falls back to 0
    and the peak keeps 2, the same with and without a server attached."""
    from koordinator_tpu import metrics

    server, _clients = rpc
    gate = threading.Event()
    entered = threading.Event()
    applied: list[str] = []

    class Stuck:
        def pod_add(self, entry, arrs):
            if entry["name"] == "first":
                entered.set()
                assert gate.wait(10), "test gate never opened"
            applied.append(entry["name"])

    service = StateSyncService()
    server.start()
    if attached:
        service.attach(server)
    service.attach_binding(Stuck())
    req = resource_vector(cpu=100, memory=64)
    first = threading.Thread(
        target=lambda: service.add_pod("first", req), daemon=True)
    first.start()
    assert entered.wait(5), "binding apply never started"
    burst = [threading.Thread(
        target=lambda t=t: [service.add_pod(f"t{t}_{i}", req)
                            for i in range(20)], daemon=True)
        for t in range(2)]
    for thread in burst:
        thread.start()
    wait_until(lambda: len(service._binding_queue) == 2)
    assert metrics.sync_binding_backlog.value() == 2.0
    gate.set()
    for thread in [first, *burst]:
        thread.join(10)
        assert not thread.is_alive()
    assert len(applied) == 41 and applied[0] == "first"
    by_rv = [e["name"] for _, e, _ in service.log.since(0)]
    assert applied == by_rv
    assert service._backlog_peak == 2
    assert metrics.sync_binding_backlog_peak.value() == 2.0
    assert metrics.sync_binding_backlog.value() == 0.0
    assert _frame_counts() == ({"no_recipient": 41} if attached else {})


# -- the run form of STATE_PUSH (node_allocatable) ---------------------------

def _alloc_rows(n: int, seed: int = 0) -> np.ndarray:
    rng = np.random.default_rng(seed)
    rows = np.zeros((n, R), np.int32)
    rows[:, 0] = 64_000
    rows[:, 1] = 65_536
    rows[:, 2:] = rng.integers(0, 50_000, (n, R - 2))
    return rows


def _run_service(server, n_nodes: int, **kw):
    service = StateSyncService(**kw)
    service.attach(server)
    names = [f"n{i}" for i in range(n_nodes)]
    for name in names:
        service.upsert_node(name, resource_vector(cpu=64_000, memory=65_536))
    return service, names


def _push_run(client, names, rows, **extra):
    return client.call(
        FrameType.STATE_PUSH,
        dict({"kind": "node_allocatable", "names": list(names)}, **extra),
        {"allocatable": rows})


def _held(service) -> dict:
    """What a service holds: stored node state, the delta log, and the
    snapshot a late HELLO would be served."""
    doc, arrays = service._snapshot()

    def strip(event):
        return {k: v for k, v in event.items() if k != "trace"}

    return {
        "rv": service.rv,
        "nodes": {name: (strip(entry["doc"]),
                         {k: v.tolist() for k, v in entry["arrays"].items()})
                  for name, entry in service.nodes.items()},
        "log": [(rv, strip(event),
                 {k: np.asarray(v).tolist() for k, v in arrs.items()})
                for rv, event, arrs in service.log.since(0)],
        "snapshot": ([strip(e) for e in doc["events"]],
                     {k: v.tolist() for k, v in arrays.items()}),
    }


@pytest.mark.parametrize("n,over", [
    (1, "wire"), (7, "wire"), (1_024, "wire"), (7, "in_process")])
def test_a_run_leaves_what_n_single_pushes_leave(rpc, tmp_path, n, over):
    """One run-form frame commits n events with consecutive rvs in name
    order; stored state, delta log and a late HELLO's snapshot equal
    those of n single pushes."""
    server, clients = rpc
    run_service, names = _run_service(server, n + 3)
    server.start()
    single_server = RpcServer(str(tmp_path / "single.sock"))
    single_service, _ = _run_service(single_server, n + 3)
    single_server.start()
    try:
        pushed, rows = names[1:n + 1][::-1], _alloc_rows(n)
        if over == "wire":
            _, doc, _ = _push_run(connect(server, clients), pushed, rows)
            single = connect(single_server, clients)
            for name, row in zip(pushed, rows):
                single.call(FrameType.STATE_PUSH,
                            {"kind": "node_allocatable", "name": name},
                            {"allocatable": row})
        else:
            rv, rejected = run_service.update_node_allocatable_run(
                pushed, rows)
            doc = {"rv": rv, "rejected": rejected}
            for name, row in zip(pushed, rows):
                single_service.update_node_allocatable(name, row)
        assert doc["rejected"] == [] and "resync" not in doc
        assert doc["rv"] == run_service.rv == single_service.rv
        tail = run_service.log.since(doc["rv"] - n)
        assert [rv for rv, _, _ in tail] == list(
            range(doc["rv"] - n + 1, doc["rv"] + 1))
        assert [event["name"] for _, event, _ in tail] == pushed
        assert _held(run_service) == _held(single_service)
        for name, row in zip(pushed, rows):
            assert run_service.nodes[name]["arrays"][
                "allocatable"].tolist() == row.tolist()
    finally:
        single_server.stop()


@pytest.mark.parametrize("case", [
    "wrong_width", "one_row_short", "a_vector", "float_matrix",
    "beyond_int32", "duplicate_names", "no_names", "too_many_names",
    "a_name_that_is_no_string", "names_on_another_kind",
    "name_with_names", "neither_name_nor_names", "no_matrix"])
def test_a_malformed_run_fails_its_call_and_commits_nothing(rpc, case):
    from koordinator_tpu.transport.wire import STATE_PUSH_RUN_MAX

    server, clients = rpc
    service, names = _run_service(server, 4)
    server.start()
    client = connect(server, clients)
    doc = {"kind": "node_allocatable", "names": names[:3]}
    arrays = {"allocatable": _alloc_rows(3)}
    if case == "wrong_width":
        arrays = {"allocatable": np.zeros((3, R + 1), np.int32)}
    elif case == "one_row_short":
        arrays = {"allocatable": _alloc_rows(2)}
    elif case == "a_vector":
        arrays = {"allocatable": _alloc_rows(3)[0]}
    elif case == "float_matrix":
        arrays = {"allocatable": _alloc_rows(3).astype(np.float32)}
    elif case == "beyond_int32":
        rows = _alloc_rows(3).astype(np.int64)
        rows[2, 3] = 2**31          # the LAST row: the first two are sound
        arrays = {"allocatable": rows}
    elif case == "duplicate_names":
        doc["names"] = [names[0], names[1], names[0]]
    elif case == "no_names":
        doc["names"], arrays = [], {"allocatable": _alloc_rows(0)}
    elif case == "too_many_names":
        many = STATE_PUSH_RUN_MAX + 1
        doc["names"] = [f"n{i}" for i in range(many)]
        arrays = {"allocatable": _alloc_rows(many)}
    elif case == "a_name_that_is_no_string":
        doc["names"] = [names[0], 7, names[1]]
    elif case == "names_on_another_kind":
        doc["kind"] = "node_usage"
        arrays = {"usage": _alloc_rows(3)}
    elif case == "name_with_names":
        doc["name"] = names[0]
    elif case == "neither_name_nor_names":
        del doc["names"]
    elif case == "no_matrix":
        arrays = {}
    before = _held(service)
    with pytest.raises(RpcError):
        client.call(FrameType.STATE_PUSH, doc, arrays)
    assert _held(service) == before
    # the connection outlives the refusal and a sound run still commits
    _, reply, _ = _push_run(client, names[:3], _alloc_rows(3))
    assert reply["rv"] == before["rv"] + 3 and reply["rejected"] == []


def test_a_run_skips_and_reports_an_unknown_name_and_commits_the_rest(rpc):
    server, clients = rpc
    service, names = _run_service(server, 4)
    server.start()
    rows = _alloc_rows(4)
    rv0 = service.rv
    _, doc, _ = _push_run(connect(server, clients),
                          [names[0], "ghost", names[2], "gone"], rows)
    assert doc["rejected"] == [["ghost", "unknown node"],
                               ["gone", "unknown node"]]
    assert doc["resync"] is True       # the single form's ERROR says so too
    assert doc["rv"] == service.rv == rv0 + 2
    assert [e["name"] for _, e, _ in service.log.since(rv0)] == [
        names[0], names[2]]
    for name, row in ((names[0], rows[0]), (names[2], rows[2])):
        assert service.nodes[name]["arrays"]["allocatable"].tolist() == \
            row.tolist()
    assert "ghost" not in service.nodes
    # all of them unknown: nothing commits, the reply names them all
    _, doc, _ = _push_run(connect(server, clients), ["a", "b"], rows[:2])
    assert doc["rv"] == service.rv == rv0 + 2
    assert [name for name, _ in doc["rejected"]] == ["a", "b"]
    # the single form of one unknown node still fails its own call
    with pytest.raises(RpcError, match="unknown node"):
        connect(server, clients).call(
            FrameType.STATE_PUSH,
            {"kind": "node_allocatable", "name": "ghost"},
            {"allocatable": rows[0]})


class AllocMirror(MirrorBinding):
    """MirrorBinding for a cluster whose allocatable moves."""

    def reset(self):
        super().reset()
        self.alloc: dict[str, list[int]] = {}

    def node_upsert(self, entry, arrs):
        super().node_upsert(entry, arrs)
        self.alloc[entry["name"]] = arrs["allocatable"].tolist()

    def node_alloc(self, entry, arrs):
        self.alloc[entry["name"]] = arrs["allocatable"].tolist()
        self.applies[entry["name"]] = self.applies.get(entry["name"], 0) + 1


def test_a_pusher_that_watches_pushes_a_whole_cluster_in_ten_frames(rpc):
    """The bring-up tick: 10,240 patches, two and a half times the delta
    log's retention, from a connection that is also a watcher.  In ten
    frames of 1,024 the echo of each is taken by the connection's sender
    before the frame's reply, so the watch's cursor is never more than a
    frame behind: it is never poisoned, never resynced by snapshot, and
    the echo of a frame is a few DELTA frames, not one per event."""
    from koordinator_tpu.transport.wire import STATE_PUSH_RUN_MAX

    server, clients = rpc
    n = 10_240
    service, names = _run_service(server, n)       # retention 4,096
    assert service.log.retention == 4_096
    server.start()
    frames: list = []
    sync = StateSyncClient(AllocMirror())
    client = connect(server, clients, timeout=60.0, on_push=lambda f: (
        frames.append(f), sync.on_push(f)))
    sync.bind_client(client)
    sync.bootstrap(client)
    wait_until(lambda: len(server._conns) == 1)
    conn = server._conns[0]
    rows = _alloc_rows(n, seed=3)
    rv0, per_frame = service.rv, []
    for lo in range(0, n, STATE_PUSH_RUN_MAX):
        seen = len(frames)
        _, doc, _ = _push_run(client, names[lo:lo + STATE_PUSH_RUN_MAX],
                              rows[lo:lo + STATE_PUSH_RUN_MAX])
        assert doc["rejected"] == []
        # the reply stood behind the echo in the connection's queue
        assert sync.rv == doc["rv"] == service.rv
        per_frame.append(len(frames) - seen)
    assert service.rv == rv0 + n
    assert client.connected and conn.alive and conn.dropped == 0
    assert sync.binding.resets == 1, "resynced by snapshot"
    assert sync.gaps == 0 and sync.skipped == 0 and not sync.needs_resync
    assert set(sync.binding.applies.values()) == {1}
    assert sync.binding.alloc == {
        name: row.tolist() for name, row in zip(names, rows)}
    # an idle connection gets the ready first event, then the run; the
    # sender may take a ready frame mid-hold and be handed one more
    assert per_frame[0] >= 2, per_frame
    assert max(per_frame) <= STATE_PUSH_RUN_MAX // 16, per_frame
    assert sum(per_frame) == len(frames) <= n // 64
    assert _sent_counts() == (len(frames), n)


def test_a_frame_longer_than_the_retention_would_poison_its_pusher(rpc):
    """Why STATE_PUSH_RUN_MAX exists: a run committed in one lock hold
    that outruns the retained log leaves the pusher's own cursor
    outside it."""
    server, clients = rpc
    service, names = _run_service(server, 64, retention=32)
    server.start()
    sync, client = _watch(server, clients, service, binding=AllocMirror())
    wait_until(lambda: len(server._conns) == 1)
    conn = server._conns[0]
    with service._lock:      # the sender cannot take a run meanwhile
        service.update_node_allocatable_run(names, _alloc_rows(64))
    wait_until(lambda: not client.connected)
    assert not conn.alive and conn.dropped == 1


def test_runs_from_three_pushers_beside_a_reporter_keep_every_watcher_whole(
        rpc):
    """Stress, time-bounded: three connections push runs over disjoint
    nodes while an in-process reporter commits single events and two
    watchers follow, with the interpreter switching threads 50 times as
    often.  Every event has its own rv, no watcher sees a gap or an event
    twice, and each ends holding what the service holds."""
    import sys

    server, clients = rpc
    service, names = _run_service(server, 96, retention=1 << 16)
    server.start()
    watchers = [_watch(server, clients, service, binding=AllocMirror())[0]
                for _ in range(2)]
    rv0, rounds, width = service.rv, 12, 32
    errors: list = []

    def pusher(k: int) -> None:
        try:
            client = connect(server, clients, timeout=30.0)
            mine = names[k * width:(k + 1) * width]
            for r in range(rounds):
                _, doc, _ = _push_run(client, mine,
                                      _alloc_rows(width, seed=100 * k + r))
                assert doc["rejected"] == []
        except Exception as e:  # noqa: BLE001 — read on the main thread
            errors.append(e)

    def reporter() -> None:
        try:
            for i in range(rounds * width):
                service.update_node_usage(
                    names[i % 96], resource_vector(cpu=1 + i, memory=1 + i))
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-4)
    try:
        threads = [threading.Thread(target=pusher, args=(k,), daemon=True)
                   for k in range(3)]
        threads.append(threading.Thread(target=reporter, daemon=True))
        for t in threads:
            t.start()
        for t in threads:
            t.join(60.0)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    assert not errors, errors
    total = 4 * rounds * width
    assert service.rv == rv0 + total
    assert [rv for rv, _, _ in service.log.since(rv0)] == list(
        range(rv0 + 1, rv0 + total + 1))
    for sync in watchers:
        wait_until(lambda: sync.rv == service.rv, timeout=30.0)
        assert sync.gaps == 0 and sync.skipped == 0
        assert sync.binding.resets == 1 and sync.binding.equals(service)
        assert set(sync.binding.applies.values()) == {rounds}
        assert sync.binding.alloc == {
            name: entry["arrays"]["allocatable"].tolist()
            for name, entry in service.nodes.items()}
