"""Control-plane robustness: retry/backoff/circuit-breaker pacing,
per-call deadline propagation + server-side shed, fail-fast on dead
streams, DeltaLog replay-window boundaries, the ERROR-frame
``resync: true`` path, rv-gap detection, and the stale-state degraded
mode.  All deterministic (fake clocks / seeded rngs) — the randomized
end-to-end counterpart is tests/test_chaos.py."""

import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from koordinator_tpu import metrics
from koordinator_tpu.api.qos import QoSClass
from koordinator_tpu.api.resources import (
    NUM_RESOURCE_DIMS,
    ResourceDim,
    resource_vector,
)
from koordinator_tpu.ops.assignment import ScoringConfig
from koordinator_tpu.scheduler import ClusterSnapshot, Scheduler
from koordinator_tpu.scheduler.snapshot import PodSpec
from koordinator_tpu.transport import (
    FaultConfig,
    FaultInjector,
    RpcClient,
    RpcDeadlineError,
    RpcError,
    RpcRemoteError,
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
from koordinator_tpu.transport.retry import (
    CLOSED,
    HALF_OPEN,
    OPEN,
    CircuitBreaker,
    RetryPolicy,
    RetrySchedule,
)
from koordinator_tpu.transport.services import SolveService, solve_remote
from koordinator_tpu.transport.wire import Frame, FrameType, encode_payload

R = NUM_RESOURCE_DIMS


def mk_scheduler(**kw):
    snap = ClusterSnapshot(capacity=16)
    cfg = ScoringConfig.default().replace(
        usage_thresholds=jnp.zeros(R, jnp.int32),
        estimator_defaults=jnp.zeros(R, jnp.int32))
    return Scheduler(snap, config=cfg, **kw)


def wait_until(pred, timeout=5.0, what="condition"):
    deadline = time.monotonic() + timeout
    while not pred() and time.monotonic() < deadline:
        time.sleep(0.005)
    assert pred(), f"{what} not reached in time"


# ---- RetryPolicy / CircuitBreaker ------------------------------------------


def test_retry_policy_backoff_grows_exponentially_and_caps():
    p = RetryPolicy(initial_backoff_s=0.5, max_backoff_s=4.0,
                    multiplier=2.0, jitter="none")
    assert [p.backoff(a) for a in range(5)] == [0.5, 1.0, 2.0, 4.0, 4.0]


def test_retry_policy_jitter_bounds():
    import random

    rng = random.Random(7)
    full = RetryPolicy(initial_backoff_s=1.0, jitter="full")
    equal = RetryPolicy(initial_backoff_s=1.0, jitter="equal")
    for _ in range(50):
        assert 0.0 <= full.backoff(0, rng) <= 1.0
        assert 0.5 <= equal.backoff(0, rng) <= 1.0


def test_retry_schedule_exhausts_max_elapsed_budget():
    t = [0.0]
    p = RetryPolicy(initial_backoff_s=1.0, multiplier=2.0,
                    jitter="none", max_elapsed_s=5.0)
    sched = RetrySchedule(p, clock=lambda: t[0])
    d1 = sched.next_delay()        # 1.0, elapsed 0 -> fits
    assert d1 == 1.0
    t[0] += d1
    d2 = sched.next_delay()        # 2.0, elapsed 1 -> fits (3 <= 5)
    assert d2 == 2.0
    t[0] += d2
    assert sched.next_delay() is None   # 4.0 would land at 7 > 5: stop


def test_breaker_opens_half_opens_and_recloses():
    t = [0.0]
    b = CircuitBreaker(target="t", failure_threshold=1, clock=lambda: t[0],
                       policy=RetryPolicy(initial_backoff_s=1.0,
                                          multiplier=2.0, jitter="none"))
    assert b.state == CLOSED and b.allow()
    b.record_failure()
    assert b.state == OPEN
    assert not b.allow()            # window 1.0s
    t[0] = 0.5
    assert not b.allow()
    t[0] = 1.0
    assert b.allow()                # the half-open probe
    assert b.state == HALF_OPEN
    assert not b.allow()            # only ONE probe per window
    b.record_failure()              # probe failed: reopen, window 2.0s
    assert b.state == OPEN
    t[0] = 2.9
    assert not b.allow()
    t[0] = 3.0
    assert b.allow()
    b.record_success()
    assert b.state == CLOSED and b.opens == 0
    # recovered breaker starts its backoff schedule over
    b.record_failure()
    t[0] += 1.0
    assert b.allow()


def test_breaker_paces_dials_logarithmically():
    """Over a T-second outage, dials are O(log T) until the cap: the
    acceptance criterion's replacement for one-dial-per-tick."""
    t = [0.0]
    b = CircuitBreaker(target="t2", failure_threshold=1, clock=lambda: t[0],
                       policy=RetryPolicy(initial_backoff_s=0.5,
                                          max_backoff_s=64.0,
                                          multiplier=2.0, jitter="none"))
    dials = 0
    while t[0] < 60.0:              # a 60s outage, "ticked" every 10ms
        if b.allow():
            dials += 1
            b.record_failure()
        t[0] += 0.01
    # geometric windows 0.5+1+2+...: ~8 dials in 60s, vs 6000 ticks
    assert dials <= 9


# ---- fault injector --------------------------------------------------------


def test_fault_injector_schedule_is_deterministic_per_seed():
    cfg = FaultConfig(send_sever_p=0.2, send_truncate_p=0.2,
                      push_drop_p=0.3, push_reorder_p=0.3)
    a = FaultInjector(seed=42, config=cfg)
    b = FaultInjector(seed=42, config=cfg)
    seq_a = [a.outbound_action(is_push=i % 2 == 0) for i in range(200)]
    seq_b = [b.outbound_action(is_push=i % 2 == 0) for i in range(200)]
    assert seq_a == seq_b
    assert any(x is not None for x in seq_a), "schedule never fired"
    c = FaultInjector(seed=43, config=cfg)
    seq_c = [c.outbound_action(is_push=i % 2 == 0) for i in range(200)]
    assert seq_a != seq_c


def test_fault_injector_heal_stops_injection():
    inj = FaultInjector(seed=1, config=FaultConfig(send_sever_p=1.0))
    assert inj.outbound_action(is_push=False) == "sever"
    inj.heal()
    assert inj.outbound_action(is_push=False) is None


def test_fault_injector_heal_resets_registered_breakers():
    """The heal seam (ISSUE 17): a drill's heal() must force-close every
    registered breaker so callers probe the healed peer NOW, instead of
    waiting out an open window that chaos backoff growth pushed far past
    the heal."""
    t = [0.0]
    b = CircuitBreaker(target="healed", failure_threshold=1,
                       clock=lambda: t[0],
                       policy=RetryPolicy(initial_backoff_s=600.0,
                                          multiplier=2.0, jitter="none"))
    inj = FaultInjector(seed=7, config=FaultConfig(send_sever_p=1.0))
    inj.register_breaker(b)
    b.record_failure()
    assert b.state == OPEN
    assert not b.allow()            # 600s window: dead until the heal
    inj.heal()
    assert b.state == CLOSED
    assert b.allow()                # probed immediately, no half-open
    assert b.opens == 0             # backoff history zeroed
    # the reset breaker starts its schedule over, not where chaos left it
    b.record_failure()
    t[0] += 600.0
    assert b.allow()


def test_injected_connect_refusal_surfaces_as_rpc_error(tmp_path):
    server = RpcServer(str(tmp_path / "s.sock"))
    server.start()
    try:
        inj = FaultInjector(seed=1,
                            config=FaultConfig(connect_refuse_p=1.0))
        client = RpcClient(server.path, faults=inj)
        with pytest.raises(ConnectionRefusedError):
            client.connect()
        assert inj.injected["connect_refuse"] == 1
    finally:
        server.stop()


def test_injected_truncation_severs_and_both_sides_recover(tmp_path):
    """A mid-write truncated client frame desyncs the server's framing;
    the connection dies loudly on both ends and a fresh connect works."""
    server = RpcServer(str(tmp_path / "t.sock"))
    server.register(FrameType.SOLVE_REQUEST,
                    lambda doc, arrays: ({"ok": True}, None))
    server.start()
    clients = []
    try:
        inj = FaultInjector(seed=3,
                            config=FaultConfig(send_truncate_p=1.0))
        client = RpcClient(server.path, faults=inj)
        client.connect()
        clients.append(client)
        with pytest.raises(RpcError, match="connection lost"):
            client.call(FrameType.SOLVE_REQUEST, {})
        assert inj.injected["client_truncate"] == 1
        wait_until(lambda: not client.connected, what="client severed")
        inj.heal()
        fresh = RpcClient(server.path, faults=inj)
        fresh.connect()
        clients.append(fresh)
        _, doc, _ = fresh.call(FrameType.SOLVE_REQUEST, {})
        assert doc == {"ok": True}
    finally:
        for c in clients:
            c.close()
        server.stop()


# ---- fail-fast + reader join (satellites) ----------------------------------


def test_call_fails_fast_when_reader_is_dead(tmp_path):
    server = RpcServer(str(tmp_path / "ff.sock"))
    server.start()
    client = RpcClient(server.path, timeout=10.0)
    client.connect()
    try:
        server.stop()                      # peer EOF kills the reader
        wait_until(lambda: not client.connected, what="reader death")
        t0 = time.monotonic()
        with pytest.raises(RpcError, match="not connected"):
            client.call(FrameType.PING, {})
        assert time.monotonic() - t0 < 1.0, (
            "dead-stream call burned toward the full timeout instead of "
            "failing fast")
    finally:
        client.close()


def test_client_close_joins_reader_thread(tmp_path):
    server = RpcServer(str(tmp_path / "join.sock"))
    server.start()
    try:
        baseline = threading.active_count()
        for _ in range(8):
            client = RpcClient(server.path)
            client.connect()
            client.close()
            assert client._reader is None or not client._reader.is_alive()
        wait_until(lambda: threading.active_count() <= baseline,
                   what="reader threads reaped")
    finally:
        server.stop()


# ---- deadline propagation --------------------------------------------------


@pytest.fixture
def solve_rpc(tmp_path):
    sched = mk_scheduler()
    sched.snapshot.upsert_node(__import__(
        "koordinator_tpu.scheduler.snapshot", fromlist=["NodeSpec"]
    ).NodeSpec(name="n0", allocatable=resource_vector(cpu=8000,
                                                      memory=16384)))
    server = RpcServer(str(tmp_path / "dl.sock"))
    service = SolveService(sched)
    service.attach(server)
    server.start()
    client = RpcClient(server.path)
    client.connect()
    try:
        yield sched, service, client
    finally:
        client.close()
        server.stop()


def test_expired_deadline_is_shed_at_the_channel(solve_rpc):
    sched, service, client = solve_rpc
    before = metrics.rpc_deadline_shed_total.value(
        labels={"type": "SOLVE_REQUEST"})
    # deadline already spent when the frame lands: shed pre-dispatch
    # (deadline_ms in the doc, not the kwarg, so the client still waits
    # for the ERROR instead of timing out locally first)
    with pytest.raises(RpcDeadlineError):
        client.call(FrameType.SOLVE_REQUEST, {"deadline_ms": -1.0})
    assert metrics.rpc_deadline_shed_total.value(
        labels={"type": "SOLVE_REQUEST"}) == before + 1
    assert service.sheds == 0              # never reached the handler


def test_solve_shed_after_burning_budget_on_the_round_lock(solve_rpc):
    """The issue's headline case: a SOLVE_REQUEST that spent its budget
    waiting for the scheduler lock is shed WITHOUT running the solve."""
    sched, service, client = solve_rpc
    sched.enqueue(PodSpec(name="p0",
                          requests=resource_vector(cpu=100, memory=128)))
    release = threading.Event()
    holding = threading.Event()

    def hog():
        with sched.lock:
            holding.set()
            release.wait(5)

    t = threading.Thread(target=hog, daemon=True)
    t.start()
    holding.wait(5)
    err = []

    def call():
        try:
            client.call(FrameType.SOLVE_REQUEST, {"deadline_ms": 100.0})
        except Exception as e:  # noqa: BLE001
            err.append(e)

    caller = threading.Thread(target=call, daemon=True)
    caller.start()
    time.sleep(0.4)                        # budget long gone
    release.set()
    caller.join(5)
    t.join(5)
    assert err and isinstance(err[0], RpcDeadlineError)
    assert service.sheds == 1
    assert "p0" in sched.pending, "shed request must not have solved"
    # a fresh in-budget call still solves
    out = solve_remote(client, deadline_ms=5000)
    assert out["assignments"] == {"p0": "n0"}


def test_request_queued_behind_slow_handler_burns_its_budget(tmp_path):
    """Handlers are sequential per connection; the eager read loop
    stamps TRUE arrival, so a request that waited out its budget in the
    inbox behind a slow handler is shed — not granted a fresh budget
    when the handler finally returns."""
    server = RpcServer(str(tmp_path / "q.sock"))
    runs = []

    def handler(doc, arrays):
        runs.append(doc.get("who"))
        if doc.get("sleep"):
            time.sleep(0.4)
        return {"ok": True}, None

    server.register(FrameType.SOLVE_REQUEST, handler)
    server.start()
    client = RpcClient(server.path)
    client.connect()
    results = {}

    def call(who, doc):
        try:
            results[who] = client.call(FrameType.SOLVE_REQUEST,
                                       dict(doc, who=who))
        except Exception as e:  # noqa: BLE001
            results[who] = e

    try:
        slow = threading.Thread(target=call,
                                args=("slow", {"sleep": True}))
        slow.start()
        time.sleep(0.1)                   # slow's handler is running
        # queued behind slow with a 100ms budget (doc field, so the
        # client waits for the server's answer instead of timing out)
        call("late", {"deadline_ms": 100.0})
        slow.join(5)
        assert results["slow"][1] == {"ok": True}
        assert isinstance(results["late"], RpcDeadlineError), results["late"]
        assert runs == ["slow"], (
            f"expired queued request still ran its handler: {runs}")
    finally:
        client.close()
        server.stop()


def test_deadline_wait_expiry_is_not_a_transport_error(solve_rpc):
    """A deadline-bounded wait that runs out raises RpcDeadlineError
    (the connection is healthy) — shared-connection owners must not
    tear the client down over a per-call budget."""
    sched, service, client = solve_rpc
    release = threading.Event()
    holding = threading.Event()

    def hog():
        with sched.lock:
            holding.set()
            release.wait(5)

    t = threading.Thread(target=hog, daemon=True)
    t.start()
    holding.wait(5)
    try:
        with pytest.raises(RpcDeadlineError):
            client.call(FrameType.SOLVE_REQUEST, {}, deadline_ms=150.0)
        assert client.connected
    finally:
        release.set()
        t.join(5)


def test_deadline_kwarg_bounds_the_client_wait(solve_rpc):
    sched, service, client = solve_rpc
    release = threading.Event()
    holding = threading.Event()

    def hog():
        with sched.lock:
            holding.set()
            release.wait(5)

    t = threading.Thread(target=hog, daemon=True)
    t.start()
    holding.wait(5)
    try:
        t0 = time.monotonic()
        with pytest.raises(RpcError):
            client.call(FrameType.SOLVE_REQUEST, {}, deadline_ms=150.0)
        assert time.monotonic() - t0 < 2.0
    finally:
        release.set()
        t.join(5)


# ---- DeltaLog replay-window boundary (satellite) ---------------------------


def test_delta_log_boundary_exact_oldest_gets_delta():
    log = DeltaLog(retention=4)
    for rv in range(1, 9):                 # retained: 5..8
        log.append(rv, {"n": rv}, {})
    assert log.oldest_rv() == 5
    # at the oldest retained event: replay the rest
    assert [e["n"] for _, e, _ in log.since(5)] == [6, 7, 8]
    # one BEFORE the oldest retained event: the client is missing
    # nothing the log lost (5.. are all retained) — still a DELTA
    assert [e["n"] for _, e, _ in log.since(4)] == [5, 6, 7, 8]
    # one event older: rv 4 was evicted — resync required
    with pytest.raises(ResyncRequired):
        log.since(3)


def test_hello_at_replay_window_boundary(tmp_path):
    """The same boundary through the wire: last_rv at the window edge
    gets DELTA, one event older gets the full SNAPSHOT."""
    server = RpcServer(str(tmp_path / "bnd.sock"))
    service = StateSyncService(retention=4)
    service.attach(server)
    server.start()
    clients = []

    def hello(last_rv):
        client = RpcClient(server.path)
        client.connect()
        clients.append(client)
        ftype, doc, arrays = client.call(FrameType.HELLO, {
            "last_rv": last_rv, "proto": 3,
            "instance": service.instance})
        return ftype, doc

    try:
        for i in range(8):                 # rv 1..8; retained 5..8
            service.upsert_node(f"n{i}",
                                resource_vector(cpu=1000, memory=1024))
        assert service.log.oldest_rv() == 5
        ftype, doc = hello(4)
        assert ftype is FrameType.DELTA
        assert [e["rv"] for e in doc["events"]] == [5, 6, 7, 8]
        ftype, doc = hello(3)              # rv 4 evicted: full snapshot
        assert ftype is FrameType.SNAPSHOT
        assert doc.get("snapshot") and len(doc["events"]) == 8
        ftype, doc = hello(8)              # fully caught up
        assert ftype is FrameType.ACK
    finally:
        for c in clients:
            c.close()
        server.stop()


# ---- ERROR resync: true end-to-end (satellite) -----------------------------


def test_unknown_node_error_carries_resync_flag(tmp_path):
    server = RpcServer(str(tmp_path / "rs.sock"))
    service = StateSyncService()
    service.attach(server)
    server.start()
    client = RpcClient(server.path)
    client.connect()
    try:
        with pytest.raises(RpcRemoteError) as ei:
            client.call(FrameType.STATE_PUSH,
                        {"kind": "node_usage", "name": "ghost"},
                        {"usage": resource_vector(cpu=1)})
        assert ei.value.resync is True
        # a plain schema error must NOT ask for resync
        with pytest.raises(RpcRemoteError) as ei:
            client.call(FrameType.STATE_PUSH,
                        {"kind": "node_usage", "name": "ghost"})
        assert ei.value.resync is False
    finally:
        client.close()
        server.stop()


def test_error_resync_rehellos_and_manager_binding_survives(tmp_path):
    """End-to-end: a manager pushing for a node the sidecar no longer
    knows gets ERROR resync:true; the reconnecting client re-HELLOs on
    the spot and the mid-stream (snapshot) resync preserves the
    koordlet-fed node_usage aggregates (hp_request/hp_max_used_req)
    instead of resetting them to over-advertising zeros."""
    from koordinator_tpu.cmd.binaries import ReconnectingSidecarClient
    from koordinator_tpu.manager.colocation_loop import ManagerSyncBinding

    server = RpcServer(str(tmp_path / "mgr.sock"))
    service = StateSyncService()
    service.attach(server)
    server.start()
    service.upsert_node("n0", resource_vector(cpu=16000, memory=16384))
    service.update_node_usage(
        "n0", resource_vector(cpu=2000, memory=4096),
        hp_request=resource_vector(cpu=3000, memory=2048),
        hp_max_used_req=resource_vector(cpu=3500, memory=2100),
        report_time=123.0)
    service.upsert_node("n1", resource_vector(cpu=8000, memory=8192))

    binding = ManagerSyncBinding()
    sync = StateSyncClient(binding)

    def bootstrap_watch(client):
        sync.bind_client(client)
        sync.bootstrap(client)

    sidecar = ReconnectingSidecarClient(
        server.path, on_push=sync.on_push, on_connect=bootstrap_watch)
    try:
        sidecar.ensure()
        assert set(binding.nodes) == {"n0", "n1"}

        # the sidecar loses n1 while the manager isn't looking (watch
        # push suppressed: simulate the lost-delta world by removing it
        # behind the client's back)
        with service._lock:
            service.nodes.pop("n1")
        # ...and force the re-HELLO down the SNAPSHOT path: pretend the
        # manager last synced a different service incarnation
        sync.instance = "stale-incarnation"
        before = sidecar.resyncs

        with pytest.raises(RpcRemoteError) as ei:
            sidecar.call(FrameType.STATE_PUSH,
                         {"kind": "node_allocatable", "name": "n1"},
                         {"allocatable": resource_vector(cpu=1)})
        assert ei.value.resync is True
        assert sidecar.resyncs == before + 1
        # the re-HELLO ran: instance healed, view re-snapshot
        assert sync.instance == service.instance
        wait_until(lambda: "n1" not in binding.nodes,
                   what="ghost node dropped by resync")
        view = binding.nodes["n0"]
        assert view.hp_request is not None, (
            "snapshot resync dropped the koordlet usage aggregates")
        assert int(view.hp_request[ResourceDim.CPU]) == 3000
        assert int(view.hp_max_used_req[ResourceDim.CPU]) == 3500
        assert view.usage_time == 123.0
        # pushes against the fresh view work again
        sidecar.call(FrameType.STATE_PUSH,
                     {"kind": "node_allocatable", "name": "n0"},
                     {"allocatable": resource_vector(cpu=16000,
                                                     memory=16384,
                                                     batch_cpu=1000)})
    finally:
        sidecar.close()
        server.stop()


# ---- rv-gap detection ------------------------------------------------------


class _FakeTransport:
    def __init__(self):
        self.closed = 0

    def close(self):
        self.closed += 1


def _delta_frame(rv):
    doc, arrays = _pack_events([(rv, {"kind": "pod_remove",
                                      "name": f"p{rv}"}, {})])
    return Frame(FrameType.DELTA, 0, encode_payload(doc, arrays))


class _NullBinding:
    def __getattr__(self, name):
        return lambda *a, **k: None


def test_rv_gap_flags_resync_and_severs_the_stream():
    sync = StateSyncClient(_NullBinding())
    fake = _FakeTransport()
    sync.bind_client(fake)
    sync.rv = 0
    sync.on_push(_delta_frame(1))
    sync.on_push(_delta_frame(2))
    assert sync.gaps == 0 and not sync.needs_resync
    sync.on_push(_delta_frame(4))          # rv 3 lost on the wire
    assert sync.gaps == 1 and sync.needs_resync
    assert fake.closed == 1
    # duplicates/overlaps stay idempotent, not gaps
    sync.on_push(_delta_frame(4))
    assert sync.gaps == 1 and sync.skipped == 1


def test_rv_gap_repair_rides_the_full_snapshot():
    """The gap handler APPLIES the fresher events, so self.rv has
    already advanced past the hole — a delta re-HELLO from last_rv
    would replay nothing and the lost event would stay lost forever
    with both rv counters agreeing.  The reconnect bootstrap must ask
    for the full snapshot instead."""
    sync = StateSyncClient(_NullBinding())
    sync.rv = 2
    sync.on_push(_delta_frame(4))          # rv 3 lost; rv now 4
    assert sync.needs_resync and sync.rv == 4

    hellos = []

    class _FakeClient:
        def call(self, ftype, doc, arrays=None):
            hellos.append(doc)
            return FrameType.ACK, {}, {}

    sync.bootstrap(_FakeClient())
    assert hellos[0]["last_rv"] == -1      # full snapshot, not a delta
    assert not sync.needs_resync           # repaired: flag cleared
    # healthy reconnects keep the cheap delta path
    sync.bootstrap(_FakeClient())
    assert hellos[1]["last_rv"] == sync.rv


# ---- stale-state degraded mode ---------------------------------------------


def _degraded_fixture():
    from koordinator_tpu.scheduler.snapshot import NodeSpec

    t = [0.0]
    sched = mk_scheduler(clock=lambda: t[0], staleness_threshold_sec=10.0)
    sched.snapshot.upsert_node(NodeSpec(
        name="n0",
        allocatable=resource_vector(cpu=64000, memory=65536,
                                    batch_cpu=10000, batch_memory=8192)))
    sched.note_sync_event()                # the feed spoke at t=0
    return t, sched


def test_stalled_feed_flips_degraded_and_suspends_be_admission():
    t, sched = _degraded_fixture()
    sched.enqueue(PodSpec(name="prod-1",
                          requests=resource_vector(cpu=1000, memory=1024)))
    sched.enqueue(PodSpec(name="be-1", qos=int(QoSClass.BE),
                          requests=resource_vector(cpu=500, memory=256)))
    sched.enqueue(PodSpec(name="batch-dim-1",
                          requests=resource_vector(batch_cpu=500,
                                                   batch_memory=256)))
    t[0] = 5.0                             # fresh enough: everything flows
    result = sched.schedule_round()
    assert not sched.degraded
    assert set(result.assignments) == {"prod-1", "be-1", "batch-dim-1"}

    sched.enqueue(PodSpec(name="prod-2",
                          requests=resource_vector(cpu=1000, memory=1024)))
    sched.enqueue(PodSpec(name="be-2", qos=int(QoSClass.BE),
                          requests=resource_vector(cpu=500, memory=256)))
    sched.enqueue(PodSpec(name="batch-dim-2",
                          requests=resource_vector(batch_cpu=500,
                                                   batch_memory=256)))
    t[0] = 16.0                            # feed silent past threshold
    result = sched.schedule_round()
    assert sched.degraded and sched.degraded_entries == 1
    assert metrics.degraded_mode.value() == 1.0
    assert metrics.state_staleness_seconds.value() == pytest.approx(16.0)
    # prod keeps scheduling; BE and batch-dim admission is suspended
    # (held pending, not failed — they resume on resync)
    assert set(result.assignments) == {"prod-2"}
    assert "be-2" in sched.pending and "batch-dim-2" in sched.pending
    assert sched.last_suspended == 2
    assert metrics.degraded_suspended_pods.value() == 2.0

    # feed heals (resync/delta applies) -> exit + suspended pods flow
    t[0] = 17.0
    sched.note_sync_event()
    result = sched.schedule_round()
    assert not sched.degraded
    assert metrics.degraded_mode.value() == 0.0
    assert set(result.assignments) == {"be-2", "batch-dim-2"}


def test_degraded_exit_has_hysteresis():
    t, sched = _degraded_fixture()
    t[0] = 11.0
    sched.schedule_round()
    assert sched.degraded
    # a single trickle event at age just under the threshold is NOT
    # enough: exit needs age <= threshold/2
    t[0] = 20.0
    sched.note_sync_event()
    t[0] = 26.0                            # age 6 > exit threshold 5
    sched.schedule_round()
    assert sched.degraded
    t[0] = 24.0 + 0.5                      # age fell under threshold/2
    sched.schedule_round()
    assert not sched.degraded


def test_degraded_forces_full_pass_over_incremental_cache():
    from koordinator_tpu.scheduler.snapshot import NodeSpec

    t = [0.0]
    sched = mk_scheduler(clock=lambda: t[0], staleness_threshold_sec=10.0,
                         batch_solver_threshold=1)
    # tiny fixture: the 2-pod/4-node dirty fractions would trip the
    # ordinary fallback and mask the path under test
    sched.incremental_dirty_threshold = 1.0
    # small static round count: the propose/accept passes unroll per
    # round, and this test exercises PATH SELECTION, not solve quality —
    # 12 unrolled rounds would triple the jit compile for nothing
    sched.kit.rounds = 2
    for i in range(4):
        sched.snapshot.upsert_node(NodeSpec(
            name=f"n{i}",
            allocatable=resource_vector(cpu=64000, memory=65536)))
    sched.note_sync_event()
    sched.enqueue(PodSpec(name="w0",
                          requests=resource_vector(cpu=100, memory=128)))
    sched.schedule_round()
    assert sched.last_solve_path == "full_cold"   # cache warms
    sched.enqueue(PodSpec(name="w1",
                          requests=resource_vector(cpu=100, memory=128)))
    t[0] = 2.0
    sched.schedule_round()
    assert sched.last_solve_path == "incremental"
    sched.enqueue(PodSpec(name="w2",
                          requests=resource_vector(cpu=100, memory=128)))
    t[0] = 15.0                            # stale: cache dropped
    sched.schedule_round()
    assert sched.degraded
    assert sched.last_solve_path == "degraded"
    assert sched._cand_cache is None
    # resync: incremental resumes from a cold rebuild
    sched.note_sync_event()
    t[0] = 15.5
    sched.enqueue(PodSpec(name="w3",
                          requests=resource_vector(cpu=100, memory=128)))
    sched.schedule_round()
    assert not sched.degraded
    assert sched.last_solve_path == "full_cold"


def test_degraded_watchdog_disabled_by_default():
    sched = mk_scheduler(clock=lambda: 1e9)
    sched.note_sync_event()
    sched.schedule_round()
    assert not sched.degraded


# ---- breaker-paced reconnecting client -------------------------------------


def test_reconnecting_client_backs_off_on_dead_sidecar(tmp_path):
    from koordinator_tpu.cmd.binaries import ReconnectingSidecarClient

    dials = [0]
    t = [0.0]
    breaker = CircuitBreaker(
        target="dead", failure_threshold=1, clock=lambda: t[0],
        policy=RetryPolicy(initial_backoff_s=1.0, multiplier=2.0,
                           jitter="none"))
    client = ReconnectingSidecarClient(
        str(tmp_path / "nobody-home.sock"), breaker=breaker)

    real_connect = RpcClient.connect

    def counting_connect(self):
        dials[0] += 1
        return real_connect(self)

    try:
        RpcClient.connect = counting_connect
        # 100 "ticks" over 10s of fake time: without the breaker this
        # was 100 dials; with it, the geometric windows allow ~5
        for _ in range(100):
            t[0] += 0.1
            with pytest.raises(RpcError):
                client.ensure()
        assert dials[0] <= 5
        assert breaker.state == OPEN
    finally:
        RpcClient.connect = real_connect
        client.close()


def test_reconnecting_client_recovers_after_breaker_window(tmp_path):
    from koordinator_tpu.cmd.binaries import ReconnectingSidecarClient

    t = [0.0]
    breaker = CircuitBreaker(
        target="rec", failure_threshold=1, clock=lambda: t[0],
        policy=RetryPolicy(initial_backoff_s=1.0, jitter="none"))
    sock = str(tmp_path / "late.sock")
    client = ReconnectingSidecarClient(sock, breaker=breaker)
    try:
        with pytest.raises(RpcError):
            client.ensure()
        server = RpcServer(sock)
        server.start()
        try:
            with pytest.raises(RpcError, match="circuit open"):
                client.ensure()            # window not yet elapsed
            t[0] = 1.0
            assert client.ensure().connected   # half-open probe succeeds
            assert breaker.state == CLOSED
        finally:
            server.stop()
    finally:
        client.close()
