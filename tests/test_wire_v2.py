"""Wire protocol v2: typed request schemas + lease frames + cross-process
leader election.

- REQUEST_SCHEMAS / validate_doc make peer skew fail loud at the server
  boundary (the api.proto versioned-contract role);
- LEASE_GET/LEASE_UPDATE + RemoteLeaseStore let two scheduler PROCESSES
  contend one lease over the transport; the failover
  test kill -9s the leading process and the standby must take over.
"""

import textwrap
import time

import pytest

from koordinator_tpu.ha import (
    InMemoryLeaseStore,
    LeaderElector,
    LeaseRecord,
    LeaseService,
    RemoteLeaseStore,
)
from koordinator_tpu.transport.channel import RpcClient, RpcError, RpcServer
from koordinator_tpu.transport.wire import (
    PROTOCOL_VERSION,
    FrameType,
    WireSchemaError,
    validate_doc,
)


class TestSchemas:
    def test_missing_required_field_raises(self):
        with pytest.raises(WireSchemaError, match="last_rv"):
            validate_doc(FrameType.HELLO, {"proto": PROTOCOL_VERSION})

    def test_wrong_type_raises(self):
        with pytest.raises(WireSchemaError, match="name"):
            validate_doc(FrameType.LEASE_GET, {"name": 7})

    def test_bool_not_accepted_as_int(self):
        with pytest.raises(WireSchemaError, match="bool"):
            validate_doc(
                FrameType.HELLO, {"last_rv": True,
                                  "proto": PROTOCOL_VERSION})

    def test_extra_fields_allowed(self):
        validate_doc(FrameType.HELLO, {
            "last_rv": 3, "proto": PROTOCOL_VERSION, "future": "field"})

    def test_unschemad_types_pass(self):
        validate_doc(FrameType.DELTA, {"anything": object()})


def _server(tmp_path, name="lease.sock"):
    path = str(tmp_path / name)
    server = RpcServer(path)
    svc = LeaseService()
    svc.attach(server)
    server.start()
    return path, server, svc


class TestLeaseFrames:
    def test_remote_get_update_roundtrip(self, tmp_path):
        path, server, svc = _server(tmp_path)
        try:
            client = RpcClient(path)
            client.connect()
            store = RemoteLeaseStore(client)
            assert store.get("sched").holder == ""
            rec = LeaseRecord(holder="a", duration_seconds=2.0,
                              acquire_time=1.0, renew_time=1.0,
                              transitions=1)
            assert store.update("sched", "", rec)
            got = store.get("sched")
            assert got.holder == "a" and got.transitions == 1
            # CAS: stale expect_holder fails
            assert not store.update(
                "sched", "b", LeaseRecord(holder="b"))
            client.close()
        finally:
            server.stop()

    def test_schema_violation_surfaces_as_rpc_error(self, tmp_path):
        path, server, svc = _server(tmp_path)
        try:
            client = RpcClient(path)
            client.connect()
            with pytest.raises(RpcError, match="missing required field"):
                client.call(FrameType.LEASE_GET, {})
            # connection survives a schema error: next call works
            assert RemoteLeaseStore(client).get("x").holder == ""
            client.close()
        finally:
            server.stop()

    def test_old_protocol_hello_rejected(self, tmp_path):
        from koordinator_tpu.transport.deltasync import StateSyncService

        path = str(tmp_path / "sync.sock")
        server = RpcServer(path)
        sync = StateSyncService()
        sync.attach(server)
        server.start()
        try:
            client = RpcClient(path)
            client.connect()
            # a v1 peer omits "proto": the schema rejects it loudly
            with pytest.raises(RpcError, match="proto"):
                client.call(FrameType.HELLO, {"last_rv": -1})
            # a mismatched advertised protocol is also rejected
            with pytest.raises(RpcError, match="incompatible"):
                client.call(FrameType.HELLO,
                            {"last_rv": -1, "proto": 99})
            client.close()
        finally:
            server.stop()

    def test_two_electors_one_leader_in_process(self, tmp_path):
        path, server, svc = _server(tmp_path)
        try:
            clients = [RpcClient(path), RpcClient(path)]
            for c in clients:
                c.connect()
            now = [100.0]
            electors = [
                LeaderElector(RemoteLeaseStore(c), "sched", ident,
                              lease_duration=5.0,
                              clock=lambda: now[0])
                for c, ident in zip(clients, ("a", "b"))
            ]
            leads = [e.tick() for e in electors]
            assert leads.count(True) == 1
            # holder crashes (no release); follower waits out the lease
            now[0] += 6.0
            standby = electors[leads.index(False)]
            assert standby.tick()
            for c in clients:
                c.close()
        finally:
            server.stop()


CONTENDER = textwrap.dedent("""
    import sys, time
    sock, ident, status = sys.argv[1], sys.argv[2], sys.argv[3]
    from koordinator_tpu.ha import LeaderElector, RemoteLeaseStore
    from koordinator_tpu.transport.channel import RpcClient

    client = RpcClient(sock)
    client.connect()
    # wall clock: cross-process contenders must share a clock domain
    elector = LeaderElector(RemoteLeaseStore(client), "sched", ident,
                            lease_duration=1.0, clock=time.time)
    rounds = 0
    while True:
        if elector.tick():
            rounds += 1
            with open(status, "a") as f:
                f.write(f"ROUND {ident} {rounds}\\n")
        time.sleep(0.05)
""")


def test_cross_process_failover_kill9(tmp_path):
    """kill -9 the leading scheduler process; the standby must acquire the
    lease and run rounds (cmd/koord-manager/main.go Leases semantics)."""
    from tests.proc_helpers import kill_all, spawn_replicas, wait_for

    path, server, svc = _server(tmp_path, "failover.sock")
    script = tmp_path / "contender.py"
    script.write_text(CONTENDER)
    status = {i: tmp_path / f"status-{i}" for i in ("a", "b")}
    for f in status.values():
        f.write_text("")
    procs, errs = spawn_replicas(
        script, {i: [path, i, str(status[i])] for i in ("a", "b")},
        tmp_path)
    try:
        def leader_now():
            return svc.store.get("sched").holder

        wait_for(lambda: bool(leader_now()), procs, errs, 60,
                 "first lease acquisition")
        first = leader_now()
        assert first in ("a", "b"), "no process acquired the lease"
        # the leader actually runs rounds
        wait_for(lambda: f"ROUND {first}" in status[first].read_text(),
                 procs, errs, 30, "leader rounds")

        procs[first].kill()          # SIGKILL: no voluntary release
        procs[first].wait(timeout=10)
        other = "b" if first == "a" else "a"
        live = {other: procs[other]}
        # standby must wait out the 1s lease, then take over and schedule
        wait_for(lambda: leader_now() == other, live, errs, 60,
                 "standby lease takeover")
        wait_for(lambda: f"ROUND {other}" in status[other].read_text(),
                 live, errs, 30, "standby rounds")
    finally:
        kill_all(procs)
        server.stop()


class TestStatePushValidation:
    """A malformed client-encoded array must fail the PUSHING call and
    never enter the replay log (where it would poison every sync
    client, including future bootstrappers)."""

    def _server(self, tmp_path):
        from koordinator_tpu.transport.channel import RpcServer
        from koordinator_tpu.transport.deltasync import StateSyncService

        server = RpcServer(str(tmp_path / "push.sock"))
        service = StateSyncService()
        service.attach(server)
        server.start()
        return server, service

    def test_wrong_shape_and_dtype_rejected(self, tmp_path):
        import numpy as np
        import pytest

        from koordinator_tpu.transport.channel import RpcClient, RpcError
        from koordinator_tpu.transport.wire import FrameType

        server, service = self._server(tmp_path)
        client = RpcClient(server.path)
        client.connect()
        try:
            for bad in (np.zeros(3, np.int32),            # wrong length
                        np.zeros((2, 10), np.int32),      # wrong rank
                        np.zeros(10, np.float32)):        # wrong dtype
                with pytest.raises(RpcError):
                    client.call(FrameType.STATE_PUSH,
                                {"kind": "node_upsert", "name": "bad"},
                                {"allocatable": bad})
            assert service.rv == 0 and not service.nodes  # nothing logged

            # nested element poisoning: a string where the reservation
            # owner matcher expects a mapping must fail the call
            with pytest.raises(RpcError, match="labels"):
                client.call(FrameType.STATE_PUSH,
                            {"kind": "rsv_upsert", "name": "r1",
                             "owners": [{"labels": "xyz"}]},
                            {"requests": np.zeros(10, np.int32)})
            with pytest.raises(RpcError, match="core"):
                client.call(FrameType.STATE_PUSH,
                            {"kind": "node_upsert", "name": "n1",
                             "devices": {"gpu": [{"core": "many"}]}},
                            {"allocatable": np.zeros(10, np.int32)})
            assert service.rv == 0 and not service.nodes

            _, doc, _ = client.call(
                FrameType.STATE_PUSH,
                {"kind": "node_upsert", "name": "good"},
                {"allocatable": np.zeros(10, np.int32)})
            assert doc["rv"] == 1 and "good" in service.nodes
        finally:
            client.close()
            server.stop()


class TestStatePushRunFormSchema:
    """STATE_PUSH asks for exactly one of ``name`` / ``names``; ``names``
    (the run form) is node_allocatable's alone, checked before anything
    commits, on the framed path and on a handler reached directly."""

    @pytest.mark.parametrize("doc,ok", [
        ({"kind": "node_allocatable", "name": "n0"}, True),
        ({"kind": "node_allocatable", "names": ["n0", "n1"]}, True),
        ({"kind": "pod_remove", "name": "p0"}, True),
        ({"kind": "node_allocatable"}, False),
        ({"kind": "node_allocatable", "name": "n0", "names": ["n1"]},
         False),
        ({"kind": "node_allocatable", "names": "n0"}, False),
        ({"kind": "node_allocatable", "name": ["n0"]}, False),
        ({"names": ["n0"]}, False),
    ])
    def test_exactly_one_of_name_and_names(self, doc, ok):
        if ok:
            validate_doc(FrameType.STATE_PUSH, doc)
        else:
            with pytest.raises(WireSchemaError):
                validate_doc(FrameType.STATE_PUSH, doc)

    @pytest.mark.parametrize("kind", [
        "node_upsert", "node_usage", "node_devices", "node_remove",
        "pod_add", "pod_remove", "rsv_upsert", "rsv_remove", "mystery"])
    def test_names_on_another_kind_is_refused_by_the_handler(self, kind):
        import numpy as np

        from koordinator_tpu.transport.deltasync import StateSyncService

        service = StateSyncService()
        service.upsert_node("n0", np.zeros(10, np.int32))
        vector = np.zeros((1, 10), np.int32)
        with pytest.raises(WireSchemaError, match="no run form"):
            service._handle_state_push(
                {"kind": kind, "names": ["n0"], "devices": {}},
                {"allocatable": vector, "usage": vector,
                 "requests": vector})
        assert service.rv == 1 and list(service.nodes) == ["n0"]

    def test_the_run_cap_is_checked_on_both_sides(self):
        import numpy as np

        from koordinator_tpu.manager.colocation_loop import (
            STATE_PUSH_RUN_MAX as senders,
        )
        from koordinator_tpu.transport import wire
        from koordinator_tpu.transport.deltasync import (
            DeltaLog,
            StateSyncService,
        )

        assert senders is wire.STATE_PUSH_RUN_MAX == 1_024
        # a frame must stay well inside what the delta log retains
        assert wire.STATE_PUSH_RUN_MAX * 4 <= DeltaLog().retention
        service = StateSyncService()
        names = [f"n{i}" for i in range(wire.STATE_PUSH_RUN_MAX + 1)]
        for name in names:
            service.upsert_node(name, np.zeros(10, np.int32))
        rows = np.ones((len(names), 10), np.int32)
        with pytest.raises(WireSchemaError, match="1 to 1024"):
            service.update_node_allocatable_run(names, rows)
        assert service.rv == len(names)
        rv, rejected = service.update_node_allocatable_run(
            names[:-1], rows[:-1])
        assert rv == 2 * len(names) - 1 and rejected == []


class TestStatePushNoPartialCommit:
    """Property: ANY state push either commits atomically (rv advances
    by one, the event replays to fresh clients) or raises WireSchemaError
    with the service byte-identical to before — never a partial write.
    Random adversarial documents/arrays via hypothesis."""

    def test_random_pushes_atomic(self):
        import numpy as np
        pytest.importorskip("hypothesis")
        from hypothesis import given, settings, strategies as st

        from koordinator_tpu.api.resources import NUM_RESOURCE_DIMS
        from koordinator_tpu.transport.deltasync import StateSyncService
        from koordinator_tpu.transport.wire import WireSchemaError

        r = NUM_RESOURCE_DIMS

        from koordinator_tpu.scheduler import ClusterSnapshot, Scheduler
        from koordinator_tpu.transport.deltasync import (
            SchedulerBinding,
            _dispatch_event,
            _unpack_event_arrays,
        )

        # ONE real scheduler binding replays every committed event: the
        # atomicity property includes "the committed event cannot crash a
        # real consumer on replay" (reservation owners, device entries)
        replay = SchedulerBinding(Scheduler(ClusterSnapshot(capacity=16)))

        json_scalars = st.one_of(
            st.none(), st.booleans(), st.integers(-2**40, 2**40),
            st.text(max_size=8))
        docs = st.fixed_dictionaries(
            {"kind": st.sampled_from(
                ["node_upsert", "node_usage", "pod_add", "pod_remove",
                 "rsv_upsert", "rsv_remove", "bogus"]),
             "name": st.text(min_size=1, max_size=8)},
            optional={
                "labels": json_scalars | st.dictionaries(
                    st.text(max_size=4), st.text(max_size=4), max_size=2),
                "owners": json_scalars | st.lists(
                    json_scalars | st.fixed_dictionaries(
                        {},
                        optional={
                            "labels": json_scalars | st.dictionaries(
                                st.text(max_size=4), st.text(max_size=4),
                                max_size=2),
                            "controller": json_scalars,
                        }),
                    max_size=2),
                "devices": json_scalars | st.dictionaries(
                    st.text(max_size=4),
                    st.lists(json_scalars | st.fixed_dictionaries(
                        {}, optional={"core": json_scalars,
                                      "memory": json_scalars}),
                             max_size=2),
                    max_size=2),
                "priority": json_scalars,
                "ttl_sec": json_scalars,
            })
        arrays = st.dictionaries(
            st.sampled_from(["allocatable", "usage", "requests"]),
            st.one_of(
                st.just(np.zeros(r, np.int32)),
                st.just(np.zeros(r - 1, np.int32)),
                st.just(np.zeros((2, r), np.int32)),
                st.just(np.zeros(r, np.float32)),
                st.just(np.full(r, 2**40, np.int64)),
            ),
            max_size=2)

        @settings(max_examples=200, deadline=None)
        @given(doc=docs, arrs=arrays)
        def check(doc, arrs):
            service = StateSyncService()
            before = (service.rv, dict(service.nodes), dict(service.pods),
                      dict(service.reservations))
            try:
                out, _ = service._handle_state_push(dict(doc), dict(arrs))
            except WireSchemaError:
                after = (service.rv, dict(service.nodes),
                         dict(service.pods), dict(service.reservations))
                assert after == before, (
                    f"rejected push mutated the service: {doc} {list(arrs)}")
            else:
                assert out["rv"] == before[0] + 1
                snapshot_doc, arrays = service._snapshot()
                assert snapshot_doc["rv"] == out["rv"]
                # the committed event must replay cleanly into a REAL
                # consumer — a commit that crashes SchedulerBinding on
                # replay poisons every client and future bootstrapper
                for entry in snapshot_doc["events"]:
                    _dispatch_event(
                        replay, entry, _unpack_event_arrays(entry, arrays))

        check()
