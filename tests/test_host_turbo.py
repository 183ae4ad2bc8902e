"""Host-plane turbo (ISSUE 19): the acceptance suite.

The contracts under test:

- **wire codec v2**: the columnar event packing round-trips to the
  exact v1 entry list for every event kind; corrupt columns fail with
  typed ``WireSchemaError``; unknown kinds fall back to v1;
- **protocol negotiation**: HELLO speaks min(peer, local) within the
  supported window — v4 peers get columnar DELTA/SNAPSHOT frames, v3
  peers keep the per-event JSON lists, out-of-window peers are
  rejected loud; a mixed-version fleet converges under the chaos
  fault layer (duplicated/reordered pushes);
- **decode zero-copy policy**: a small decoded array no longer pins
  the whole frame payload (the 4-byte-array-holds-a-multi-MB-snapshot
  aliasing bug);
- **vectorized deltasync apply**: contiguous same-kind event runs
  route through one batched binding apply that is bit-identical to
  the per-event loop;
- **batched bind commits**: one batched commit per round produces the
  same bound registry, quota charges, and per-pod surfaces as the
  sequential ``_commit_bind`` loop;
- **quality tenants in the tenant-axis program**: ``lp``-mode tenants
  join the batched cycle (their own vmapped ``lp_pack_assign``
  program) and bind exactly what serial per-tenant execution binds.
"""

import json
import socket
import struct
import sys
import threading
import time

import numpy as np
import pytest

from koordinator_tpu.transport import deltasync, wire
from koordinator_tpu.transport.channel import (
    RpcClient,
    RpcError,
    RpcRemoteError,
    RpcServer,
)
from koordinator_tpu.transport.deltasync import (
    SchedulerBinding,
    StateSyncClient,
    StateSyncService,
    _decode_events,
    _dispatch_event,
    _dispatch_events,
    _pack_events,
    _pack_events_v2,
    _unpack_event_arrays,
)
from koordinator_tpu.transport.wire import FrameType, WireSchemaError


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def _r(**kw):
    from koordinator_tpu.api.resources import resource_vector

    return resource_vector(**kw)


def _all_kind_events():
    """One event of every kind, with both default and non-default doc
    fields exercised."""
    return [
        (1, {"kind": deltasync.NODE_UPSERT, "name": "n0",
             "labels": {"rack": "r1"}, "taints": {}, "annotations": {},
             "devices": {}},
         {"allocatable": np.arange(4, dtype=np.int32),
          "usage": np.zeros(4, np.int32)}),
        (2, {"kind": deltasync.NODE_USAGE, "name": "n0"},
         {"usage": np.ones(4, np.int32),
          "agg_usage": np.full(4, 2, np.int32)}),
        (3, {"kind": deltasync.NODE_ALLOC, "name": "n0"},
         {"allocatable": np.full(4, 9, np.int32)}),
        (4, {"kind": deltasync.NODE_DEVICES, "name": "n0",
             "devices": {"gpu": [{"core": 100, "memory": 8,
                                  "group": "g0"}]}}, {}),
        (5, {"kind": deltasync.POD_ADD, "name": "p0", "priority": 7,
             "quota": "q", "gang": None, "node_selector": {},
             "labels": {"team": "x"}, "owner": None, "qos": 0},
         {"requests": np.ones(4, np.int32)}),
        (6, {"kind": deltasync.POD_REMOVE, "name": "p0"}, {}),
        (7, {"kind": deltasync.RSV_UPSERT, "name": "rsv0",
             "owners": [{"labels": {"team": "x"}}],
             "allocate_once": False, "ttl_sec": None, "node": None,
             "node_selector": {}, "tolerations": {},
             "restricted": True},
         {"requests": np.ones(4, np.int64)}),
        (8, {"kind": deltasync.RSV_REMOVE, "name": "rsv0"}, {}),
        (9, {"kind": deltasync.NODE_REMOVE, "name": "n0"}, {}),
    ]


def _sync_server(tmp_path, name="sync.sock", faults=None):
    path = str(tmp_path / name)
    server = RpcServer(path, faults=faults)
    service = StateSyncService()
    service.attach(server)
    server.start()
    return path, server, service


def _scheduler(capacity=16, quota=False, **kw):
    from koordinator_tpu.api.resources import NUM_RESOURCE_DIMS
    from koordinator_tpu.quota.tree import UNBOUNDED, QuotaTree
    from koordinator_tpu.scheduler import ClusterSnapshot, Scheduler

    tree = None
    if quota:
        total = np.zeros(NUM_RESOURCE_DIMS, np.int64)
        total[0] = 500_000
        tree = QuotaTree(total)
        mx = np.full(NUM_RESOURCE_DIMS, UNBOUNDED, np.int64)
        tree.add("q", min=np.zeros(NUM_RESOURCE_DIMS, np.int64), max=mx)
        tree.add("q2", min=np.zeros(NUM_RESOURCE_DIMS, np.int64), max=mx)
    return Scheduler(ClusterSnapshot(capacity=capacity),
                     quota_tree=tree, **kw)


def _feed_nodes(sched, n=8, seed=5):
    from koordinator_tpu.scheduler.snapshot import NodeSpec

    rng = np.random.default_rng(seed)
    for i in range(n):
        sched.snapshot.upsert_node(NodeSpec(
            name=f"n{i}",
            allocatable=_r(cpu=int(rng.integers(8_000, 32_000)),
                           memory=int(rng.integers(16_384, 65_536))),
            usage=_r(cpu=int(rng.integers(0, 1_000)),
                     memory=int(rng.integers(0, 2_048)))))


def _pod(seed, name, quota=None, non_preemptible=False):
    from koordinator_tpu.scheduler.snapshot import PodSpec

    rng = np.random.default_rng(seed)
    return PodSpec(
        name=name,
        requests=_r(cpu=int(rng.integers(200, 2_000)),
                    memory=int(rng.integers(256, 4_096))),
        priority=int(rng.integers(3_000, 9_999)),
        quota=quota, non_preemptible=non_preemptible)


# ---------------------------------------------------------------------------
# wire codec v2
# ---------------------------------------------------------------------------


class TestWireCodecV2:
    def test_columnar_roundtrip_identical_all_kinds(self):
        """v2 pack -> wire encode -> decode -> unpack reconstructs the
        EXACT v1 entry list (docs and arrays), for every event kind."""
        events = _all_kind_events()
        d1, a1 = _pack_events(events)
        packed = _pack_events_v2(events)
        assert packed is not None
        d2, a2 = packed
        d2r, a2r = wire.decode_payload(wire.encode_payload(dict(d2), a2))
        assert _decode_events(d2r, a2r) == d1["events"]
        for key, block in a1.items():
            np.testing.assert_array_equal(block, a2r[key])
        # per-event array extraction works unchanged on v2 blocks
        for entry in _decode_events(d2r, a2r):
            _unpack_event_arrays(entry, a2r)

    def test_hot_kinds_carry_no_extras(self):
        """Steady-state kinds (node_usage, pod_remove) must ride pure
        columns — zero per-event JSON."""
        events = [(i, {"kind": deltasync.NODE_USAGE, "name": f"n{i}"},
                   {"usage": np.ones(4, np.int32)}) for i in range(64)]
        doc, _ = _pack_events_v2(events)
        assert doc == {"events_v2": 64}

    def test_unknown_kind_falls_back_to_v1(self):
        assert _pack_events_v2(
            [(1, {"kind": "future_kind", "name": "x"}, {})]) is None

    def test_missing_column_raises_schema_error(self):
        doc, arrays = _pack_events_v2(_all_kind_events())
        broken = {k: v for k, v in arrays.items() if k != "__kinds__"}
        with pytest.raises(WireSchemaError, match="__kinds__"):
            _decode_events(doc, broken)

    def test_corrupt_string_column_raises_schema_error(self):
        doc, arrays = _pack_events_v2(_all_kind_events())
        arrays = dict(arrays)
        arrays["__name_blob__"] = arrays["__name_blob__"][:-2]
        with pytest.raises(WireSchemaError, match="lengths sum"):
            _decode_events(doc, arrays)


# ---------------------------------------------------------------------------
# decode_payload zero-copy policy (satellite)
# ---------------------------------------------------------------------------


class TestDecodeAliasing:
    def test_small_array_does_not_pin_payload(self):
        """The regression this satellite fixes: decoding a payload that
        carries one huge and one tiny array must not leave the tiny
        array's lifetime pinning the whole payload buffer."""
        big = np.arange(1 << 20, dtype=np.uint8)
        small = np.arange(4, dtype=np.int32)
        payload = wire.encode_payload({}, {"big": big, "small": small})
        base_refs = sys.getrefcount(payload)
        _doc, arrays = wire.decode_payload(payload)
        # the small array was copied out: no buffer aliasing at all
        assert arrays["small"].base is None
        np.testing.assert_array_equal(arrays["small"], small)
        # keep ONLY the small array; the payload's refcount must fall
        # back to its baseline (nothing but our local name holds it)
        keep = arrays["small"]
        del arrays, _doc
        assert sys.getrefcount(payload) == base_refs
        np.testing.assert_array_equal(keep, small)

    def test_dominant_array_stays_zero_copy(self):
        """The majority block keeps the zero-copy view — copying a
        multi-MB snapshot block would re-introduce the codec cost the
        framing exists to avoid."""
        big = np.arange(1 << 20, dtype=np.uint8)
        payload = wire.encode_payload({}, {"big": big})
        _doc, arrays = wire.decode_payload(payload)
        assert arrays["big"].base is not None
        np.testing.assert_array_equal(arrays["big"], big)


# ---------------------------------------------------------------------------
# protocol negotiation (satellite)
# ---------------------------------------------------------------------------


class TestHelloNegotiation:
    def test_v4_peer_gets_columnar_snapshot(self, tmp_path):
        path, server, service = _sync_server(tmp_path)
        try:
            service.upsert_node("n0", _r(cpu=1000, memory=1024))
            client = RpcClient(path)
            client.connect()
            ftype, doc, arrays = client.call(
                FrameType.HELLO,
                {"last_rv": -1, "proto": wire.PROTOCOL_VERSION})
            assert ftype is FrameType.SNAPSHOT
            assert doc["proto"] == wire.PROTOCOL_VERSION
            assert "events_v2" in doc and "events" not in doc
            assert "__kinds__" in arrays
            client.close()
        finally:
            server.stop()

    def test_v3_peer_gets_v1_events(self, tmp_path):
        path, server, service = _sync_server(tmp_path)
        try:
            service.upsert_node("n0", _r(cpu=1000, memory=1024))
            client = RpcClient(path)
            client.connect()
            ftype, doc, arrays = client.call(
                FrameType.HELLO,
                {"last_rv": -1, "proto": wire.MIN_PROTOCOL_VERSION})
            assert ftype is FrameType.SNAPSHOT
            assert doc["proto"] == wire.MIN_PROTOCOL_VERSION
            assert "events" in doc and "events_v2" not in doc
            client.close()
        finally:
            server.stop()

    def test_outside_window_rejected(self, tmp_path):
        path, server, _service = _sync_server(tmp_path)
        try:
            client = RpcClient(path)
            client.connect()
            for bad in (wire.MIN_PROTOCOL_VERSION - 1,
                        wire.PROTOCOL_VERSION + 1):
                with pytest.raises(RpcError, match="incompatible"):
                    client.call(FrameType.HELLO,
                                {"last_rv": -1, "proto": bad})
            client.close()
        finally:
            server.stop()

    def test_v3_conn_receives_legacy_delta_broadcasts(self, tmp_path):
        """A negotiated-down peer must keep receiving DELTA pushes it
        can decode: the broadcast dual-frame path."""
        path, server, service = _sync_server(tmp_path)
        try:
            sched = _scheduler()
            sync = StateSyncClient(SchedulerBinding(sched))
            frames: list[dict] = []
            seen = threading.Event()

            def on_push(frame):
                doc, arrays = wire.decode_payload(frame.payload)
                frames.append(doc)
                sync._apply(doc, arrays)
                seen.set()

            client = RpcClient(path, on_push=on_push)
            client.connect()
            # manual v3 bootstrap (the shape an old client's HELLO has)
            ftype, doc, arrays = client.call(
                FrameType.HELLO,
                {"last_rv": -1, "proto": wire.MIN_PROTOCOL_VERSION})
            sync._apply(doc, arrays, from_bootstrap=True)
            service.upsert_node("n0", _r(cpu=4000, memory=4096))
            assert seen.wait(5.0)
            # the push was the LEGACY v1 form, and it applied
            assert all("events" in f and "events_v2" not in f
                       for f in frames)
            assert "n0" in sched.snapshot.node_index
            client.close()
        finally:
            server.stop()

    def test_mixed_version_soak_under_faults(self, tmp_path):
        """A v4 client and a v3 client ride the same broadcast stream
        while the chaos layer duplicates/delays pushes; both must
        converge to the service's exact state (duplicates are absorbed
        by the rv guard on BOTH protocol versions). Reorder faults are
        deliberately absent: they require the full gap->resync re-dial
        machinery, which the hand-rolled v3 half of this harness does
        not implement (test_chaos covers that path end to end)."""
        from koordinator_tpu.transport.faults import (
            FaultConfig,
            FaultInjector,
        )

        inj = FaultInjector(seed=7, config=FaultConfig(
            push_duplicate_p=0.3, push_delay_p=0.2, push_delay_ms=1.0))
        path, server, service = _sync_server(tmp_path, faults=inj)
        clients = []
        try:
            scheds = [_scheduler(), _scheduler()]
            syncs = [StateSyncClient(SchedulerBinding(s)) for s in scheds]
            # client 0: modern v4 bootstrap; client 1: v3 peer
            c0 = RpcClient(path, on_push=syncs[0].on_push)
            c0.connect()
            clients.append(c0)
            syncs[0].bootstrap(c0)
            assert syncs[0].proto == wire.PROTOCOL_VERSION

            def v3_push(frame):
                if frame.type is FrameType.DELTA:
                    doc, arrays = wire.decode_payload(frame.payload)
                    assert "events_v2" not in doc  # legacy stream
                    syncs[1]._apply(doc, arrays)

            c1 = RpcClient(path, on_push=v3_push)
            c1.connect()
            clients.append(c1)
            ftype, doc, arrays = c1.call(
                FrameType.HELLO,
                {"last_rv": -1, "proto": wire.MIN_PROTOCOL_VERSION})
            if ftype is not FrameType.ACK:
                syncs[1]._apply(doc, arrays, from_bootstrap=True)

            for i in range(24):
                service.upsert_node(f"n{i % 6}",
                                    _r(cpu=1000 + i, memory=1024))
                service.update_node_usage(f"n{i % 6}",
                                          _r(cpu=i * 7, memory=i))
                if i % 3 == 0:
                    service.add_pod(f"p{i}", _r(cpu=100, memory=64))
            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline:
                if all(s.rv == service.rv for s in syncs):
                    break
                time.sleep(0.05)
            inj.heal()
            assert sum(inj.injected.values()) > 0, "no faults fired"
            for sync, sched in zip(syncs, scheds):
                assert sync.rv == service.rv
                assert set(sched.snapshot.node_index) == set(service.nodes)
                assert set(sched.pending) == set(service.pods)
            # the two replicas agree row-for-row with each other
            for name in scheds[0].snapshot.node_index:
                s0 = scheds[0].snapshot.node_specs[name]
                s1 = scheds[1].snapshot.node_specs[name]
                np.testing.assert_array_equal(s0.usage, s1.usage)
                np.testing.assert_array_equal(s0.allocatable,
                                              s1.allocatable)
        finally:
            for c in clients:
                c.close()
            server.stop()

    def test_corrupt_manifest_frame_typed_rejection(self, tmp_path):
        """A frame whose array manifest points outside the payload must
        fail THAT call with a schema-flagged ERROR frame — the
        connection survives and keeps serving."""
        path, server, service = _sync_server(tmp_path)
        try:
            meta = {"kind": "node_upsert", "name": "x", "__arrays__": [
                {"key": "allocatable", "dtype": "<i4", "shape": [4],
                 "offset": 1 << 20, "nbytes": 16}]}
            j = json.dumps(meta).encode()
            payload = struct.pack("<I", len(j)) + j
            frame = wire.Frame(FrameType.STATE_PUSH, 3, payload)
            sock = socket.socket(socket.AF_UNIX)
            sock.connect(path)
            sock.sendall(frame.encode())

            def recv_exact(n):
                buf = b""
                while len(buf) < n:
                    chunk = sock.recv(n - len(buf))
                    if not chunk:
                        raise ConnectionError("peer closed")
                    buf += chunk
                return buf

            reply = wire.read_frame(recv_exact)
            assert reply.type is FrameType.ERROR
            err_doc, _ = wire.decode_payload(reply.payload)
            assert err_doc.get("schema") is True
            assert "payload" in err_doc["message"]
            # same socket, valid frame: the connection was NOT torn down
            hello = wire.Frame(FrameType.HELLO, 4, wire.encode_payload(
                {"last_rv": -1, "proto": wire.PROTOCOL_VERSION}))
            sock.sendall(hello.encode())
            reply2 = wire.read_frame(recv_exact)
            assert reply2.type is FrameType.SNAPSHOT
            sock.close()
            # and the corrupt push never entered the log
            assert service.rv == 0
        finally:
            server.stop()

    def test_new_client_downgrades_against_old_server(self, tmp_path):
        """bootstrap() retries once at MIN_PROTOCOL_VERSION when the
        server rejects our advertised version as incompatible — the
        new-client-vs-old-server half of the mixed-version matrix."""
        path = str(tmp_path / "old.sock")
        server = RpcServer(path)

        def old_hello(doc, arrays):
            # a pre-negotiation server: equality or bust
            if int(doc.get("proto", 1)) != wire.MIN_PROTOCOL_VERSION:
                raise WireSchemaError(
                    f"incompatible message protocol: peer "
                    f"{doc.get('proto')}, local "
                    f"{wire.MIN_PROTOCOL_VERSION}")
            out, arrs = _pack_events([])
            out["__type__"] = int(FrameType.DELTA)
            out["rv"] = -1
            return out, arrs

        server.register(FrameType.HELLO, old_hello)
        server.start()
        try:
            sync = StateSyncClient(SchedulerBinding(_scheduler()))
            client = RpcClient(path, on_push=sync.on_push)
            client.connect()
            sync.bootstrap(client)
            assert sync.proto == wire.MIN_PROTOCOL_VERSION
            client.close()
        finally:
            server.stop()


# ---------------------------------------------------------------------------
# vectorized deltasync apply
# ---------------------------------------------------------------------------


def _usage_items(k=16, nodes=4, seed=3):
    rng = np.random.default_rng(seed)
    items = []
    for i in range(k):
        entry = {"kind": deltasync.NODE_USAGE, "name": f"n{i % nodes}",
                 "rv": i + 1}
        arrs = {"usage": _r(cpu=int(rng.integers(0, 4_000)),
                            memory=int(rng.integers(0, 8_192)))}
        items.append((entry, arrs))
    return items


class TestRunBatchedApply:
    def test_node_usage_run_identical_to_sequential(self):
        batched, serial = _scheduler(), _scheduler()
        _feed_nodes(batched), _feed_nodes(serial)
        items = _usage_items(k=24)
        _dispatch_events(SchedulerBinding(batched), items)
        for entry, arrs in items:
            _dispatch_event(SchedulerBinding(serial), entry, arrs)
        for name in serial.snapshot.node_index:
            np.testing.assert_array_equal(
                batched.snapshot.node_specs[name].usage,
                serial.snapshot.node_specs[name].usage)
        np.testing.assert_array_equal(
            np.asarray(batched.snapshot.state.node_usage),
            np.asarray(serial.snapshot.state.node_usage))

    def test_pod_add_run_identical_to_sequential(self):
        batched, serial = _scheduler(), _scheduler()
        items = []
        for i in range(12):
            items.append((
                {"kind": deltasync.POD_ADD, "name": f"p{i}",
                 "priority": i, "rv": i + 1},
                {"requests": _r(cpu=100 + i, memory=64)}))
        _dispatch_events(SchedulerBinding(batched), items)
        for entry, arrs in items:
            _dispatch_event(SchedulerBinding(serial), entry, arrs)
        assert list(batched.pending) == list(serial.pending)
        for name in serial.pending:
            assert (batched.pending[name].priority
                    == serial.pending[name].priority)
            np.testing.assert_array_equal(
                batched.pending[name].requests,
                serial.pending[name].requests)

    def test_mixed_kind_stream_preserves_order(self):
        """Runs never cross a kind boundary: a usage refresh AFTER a
        node upsert must see the upsert's allocatable (and vice versa),
        exactly as sequential dispatch orders them."""
        batched, serial = _scheduler(), _scheduler()
        stream = []
        rv = 0
        for i in range(4):
            rv += 1
            stream.append((
                {"kind": deltasync.NODE_UPSERT, "name": f"n{i}",
                 "rv": rv, "labels": {}, "taints": {},
                 "annotations": {}, "devices": {}},
                {"allocatable": _r(cpu=10_000, memory=16_384),
                 "usage": _r()}))
        for i in range(8):
            rv += 1
            stream.append((
                {"kind": deltasync.NODE_USAGE, "name": f"n{i % 4}",
                 "rv": rv},
                {"usage": _r(cpu=100 * i, memory=50 * i)}))
        rv += 1
        stream.append((
            {"kind": deltasync.NODE_UPSERT, "name": "n1", "rv": rv,
             "labels": {}, "taints": {}, "annotations": {},
             "devices": {}},
            {"allocatable": _r(cpu=20_000, memory=32_768),
             "usage": _r(cpu=1, memory=1)}))
        for i in range(6):
            rv += 1
            stream.append((
                {"kind": deltasync.POD_ADD, "name": f"p{i}", "rv": rv,
                 "priority": 1},
                {"requests": _r(cpu=100, memory=64)}))
        _dispatch_events(SchedulerBinding(batched), stream)
        for entry, arrs in stream:
            _dispatch_event(SchedulerBinding(serial), entry, arrs)
        np.testing.assert_array_equal(
            np.asarray(batched.snapshot.state.node_usage),
            np.asarray(serial.snapshot.state.node_usage))
        np.testing.assert_array_equal(
            np.asarray(batched.snapshot.state.node_allocatable),
            np.asarray(serial.snapshot.state.node_allocatable))
        assert list(batched.pending) == list(serial.pending)

    def test_run_takes_one_lock_roundtrip(self):
        sched = _scheduler()
        _feed_nodes(sched)
        binding = SchedulerBinding(sched)
        acquisitions = []
        real_lock = sched.lock

        class CountingLock:
            def __enter__(self):
                acquisitions.append(1)
                return real_lock.__enter__()

            def __exit__(self, *a):
                return real_lock.__exit__(*a)

        sched.lock = CountingLock()
        _dispatch_events(binding, _usage_items(k=24))
        assert len(acquisitions) == 1

    def test_node_alloc_run_identical_to_sequential(self):
        """A frame of the manager's patches: the run apply leaves specs
        and device rows bit-identical to the serial one, a later patch
        of a node winning as it would serially, an unknown node
        dropped."""
        batched, serial = _scheduler(), _scheduler()
        _feed_nodes(batched), _feed_nodes(serial)
        rng = np.random.default_rng(11)
        items = []
        for i in range(24):
            name = "ghost" if i == 5 else f"n{i % 8}"
            items.append((
                {"kind": deltasync.NODE_ALLOC, "name": name, "rv": i + 1},
                {"allocatable": _r(
                    cpu=20_000, memory=32_768,
                    batch_cpu=int(rng.integers(0, 9_000)),
                    batch_memory=int(rng.integers(0, 9_000)))}))
        binding = SchedulerBinding(batched)
        calls = []
        orig = binding.node_alloc_run
        binding.node_alloc_run = (
            lambda run: (calls.append(len(run)), orig(run)))
        _dispatch_events(binding, items)
        assert calls == [24]
        for entry, arrs in items:
            _dispatch_event(SchedulerBinding(serial), entry, arrs)
        assert list(batched.snapshot.node_specs) == list(
            serial.snapshot.node_specs)
        for name, spec in serial.snapshot.node_specs.items():
            got = batched.snapshot.node_specs[name]
            np.testing.assert_array_equal(got.allocatable, spec.allocatable)
            np.testing.assert_array_equal(got.usage, spec.usage)
        np.testing.assert_array_equal(
            np.asarray(batched.snapshot.state.node_allocatable),
            np.asarray(serial.snapshot.state.node_allocatable))
        np.testing.assert_array_equal(
            np.asarray(batched.snapshot.state.node_usage),
            np.asarray(serial.snapshot.state.node_usage))

    def test_equally_stamped_events_run_under_one_span(self):
        """Events that carry the SAME trace context (the events of one
        run-form frame) are one run under one sync.<kind> span joined to
        that context; a differently stamped neighbour, or a stamped
        event between unstamped ones, still ends a run, so the apply
        order stays the per-event order."""
        from koordinator_tpu import tracing
        from koordinator_tpu.api.resources import ResourceDim

        sched = _scheduler()
        _feed_nodes(sched)
        binding = SchedulerBinding(sched)
        runs = []
        orig = binding.node_alloc_run
        binding.node_alloc_run = (
            lambda run: (runs.append([e["name"] for e, _ in run]),
                         runs.append(tracing.current_context()),
                         orig(run)))
        singles = []
        orig_one = SchedulerBinding.node_alloc
        binding.node_alloc = (
            lambda entry, arrs: (singles.append(entry["name"]),
                                 orig_one(binding, entry, arrs)))
        ctx_a = tracing.TraceContext(trace_id="a" * 32, span_id="1" * 16)
        ctx_b = tracing.TraceContext(trace_id="b" * 32, span_id="2" * 16)
        stamps = ([ctx_a] * 3 + [ctx_b] + [None] * 2 + [ctx_a] + [None]
                  + [ctx_b] * 2)
        items = []
        for i, ctx in enumerate(stamps):
            entry = {"kind": deltasync.NODE_ALLOC, "name": f"n{i % 8}",
                     "rv": i + 1}
            if ctx is not None:
                # each event its own dict, as _commit_locked stamps them
                entry[tracing.TRACE_DOC_KEY] = ctx.to_doc()
            items.append((entry, {"allocatable": _r(
                cpu=20_000, memory=32_768, batch_cpu=100 + i)}))
        exporter = tracing.InMemoryExporter()
        tracing.TRACER.add_exporter(exporter)
        try:
            _dispatch_events(binding, items)
        finally:
            tracing.TRACER.remove_exporter(exporter)
        # the three runs, each applied with its context active
        assert runs[0::2] == [["n0", "n1", "n2"], ["n4", "n5"],
                              ["n0", "n1"]]
        assert [c and c.trace_id for c in runs[1::2]] == [
            ctx_a.trace_id, None, ctx_b.trace_id]
        # what stood alone went the per-event way, in its place (the
        # runs' members pass through node_alloc too: the run applies
        # each event as the single apply does)
        assert singles == [f"n{i % 8}" for i in range(len(stamps))]
        spans = exporter.find(name="sync.node_allocatable")
        assert [(s.trace_id, s.parent_id, s.attributes.get("n"),
                 s.attributes.get("first"), s.attributes.get("last"),
                 s.attributes.get("name")) for s in spans] == [
            (ctx_a.trace_id, ctx_a.span_id, 3, "n0", "n2", None),
            (ctx_b.trace_id, ctx_b.span_id, None, None, None, "n3"),
            (ctx_a.trace_id, ctx_a.span_id, None, None, None, "n6"),
            (ctx_b.trace_id, ctx_b.span_id, 2, "n0", "n1", None)]
        assert all(s.service == "scheduler" for s in spans)
        # serial order: the last patch of every node is what it holds
        for i in range(len(stamps) - 8, len(stamps)):
            assert int(sched.snapshot.node_specs[f"n{i % 8}"].allocatable[
                ResourceDim.BATCH_CPU]) == 100 + i

    def test_client_apply_routes_batched(self):
        """A DELTA batch arriving through StateSyncClient._apply (the
        replay/bootstrap path) hits the run-batched dispatch."""
        sched = _scheduler()
        _feed_nodes(sched)
        binding = SchedulerBinding(sched)
        sync = StateSyncClient(binding)
        calls = []
        orig = binding.node_usage_run
        binding.node_usage_run = (
            lambda items: (calls.append(len(items)), orig(items)))
        events = [(i + 1, e, a)
                  for i, (e, a) in enumerate(_usage_items(k=10))]
        for rv, e, a in events:
            e.pop("rv")
        doc, arrays = _pack_events(events)
        doc["rv"] = len(events)
        applied = sync._apply(doc, arrays)
        assert applied == 10
        assert calls == [10]


# ---------------------------------------------------------------------------
# batched bind commits
# ---------------------------------------------------------------------------


class TestBatchedBindCommit:
    def _seeded_pair(self):
        pair = []
        for _ in range(2):
            sched = _scheduler(quota=True)
            _feed_nodes(sched)
            pair.append(sched)
        binds = []
        for i in range(12):
            quota = ("q" if i % 3 == 0 else "q2" if i % 3 == 1 else None)
            pod = _pod(100 + i, f"p{i}", quota=quota,
                       non_preemptible=(i % 4 == 0))
            binds.append((pod, f"n{i % 8}"))
        return pair, binds

    def test_batch_identical_to_sequential_loop(self):
        from koordinator_tpu.scheduler.scheduler import SchedulingResult

        (batched, serial), binds = self._seeded_pair()
        for sched in (batched, serial):
            for pod, _node in binds:
                sched.enqueue(pod)
        res_b = SchedulingResult(assignments={}, failures={})
        res_s = SchedulingResult(assignments={}, failures={})
        batched._commit_bind_batch(binds, res_b)
        for pod, node in binds:
            serial._commit_bind(pod, node, res_s)
        assert res_b.assignments == res_s.assignments
        assert set(batched.bound) == set(serial.bound)
        for name in serial.bound:
            b, s = batched.bound[name], serial.bound[name]
            assert (b.node, b.quota, b.non_preemptible, b.priority) == \
                (s.node, s.quota, s.non_preemptible, s.priority)
            np.testing.assert_array_equal(b.requests, s.requests)
        for qname in ("q", "q2"):
            np.testing.assert_array_equal(
                batched.quota_tree.nodes[qname].used,
                serial.quota_tree.nodes[qname].used)
            np.testing.assert_array_equal(
                batched.quota_tree.nodes[qname].non_preemptible_used,
                serial.quota_tree.nodes[qname].non_preemptible_used)
        assert set(batched.pending) == set(serial.pending) == set()

    def test_bind_batch_fn_called_once_per_round(self):
        calls = []
        sched = _scheduler(quota=True, batch_solver_threshold=1,
                           bind_batch_fn=lambda b: calls.append(b),
                           bind_fn=lambda p, n: calls.append("PER-POD"))
        _feed_nodes(sched)
        for i in range(6):
            sched.enqueue(_pod(300 + i, f"p{i}", quota="q"))
        result = sched.schedule_round()
        assert len(result.assignments) == 6
        assert len(calls) == 1 and "PER-POD" not in calls
        assert sorted(calls[0]) == sorted(result.assignments.items())

    def test_round_path_unchanged_binds(self):
        """End-to-end: two identical schedulers, one round each — the
        (now batched) Bind phase decides and charges exactly what the
        round always did (covered against the whole existing suite; the
        explicit pairing here guards the batch-vs-loop seam)."""
        a = _scheduler(quota=True, batch_solver_threshold=1)
        b = _scheduler(quota=True, batch_solver_threshold=1)
        for sched in (a, b):
            _feed_nodes(sched)
            for i in range(10):
                sched.enqueue(_pod(500 + i, f"p{i}",
                                   quota=("q" if i % 2 else None)))
        ra, rb = a.schedule_round(), b.schedule_round()
        assert ra.assignments == rb.assignments
        np.testing.assert_array_equal(a.quota_tree.nodes["q"].used,
                                      b.quota_tree.nodes["q"].used)


# ---------------------------------------------------------------------------
# quality tenants in the tenant-axis program
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def kit_off():
    from koordinator_tpu.scheduler.solver_kit import SolverKit

    return SolverKit(mesh="off")


def _front(kit, modes, batch_tenant_axis):
    from koordinator_tpu.scheduler.tenancy import (
        TenantScheduler,
        TenantSpec,
    )

    front = TenantScheduler(solver_kit=kit, cycle_pod_budget=1 << 20,
                            batch_tenant_axis=batch_tenant_axis,
                            pipeline=batch_tenant_axis)
    for name, mode in modes.items():
        front.add_tenant(
            TenantSpec(name=name, weight=1.0, node_capacity=16),
            batch_solver_threshold=1, quality_mode=mode)
    return front


def _seed_front(front, pods_per_tenant=8, base=0):
    for ti, tenant in enumerate(front.tenants()):
        _feed_nodes(tenant.scheduler, n=10, seed=31 + ti)
        for j in range(pods_per_tenant):
            tenant.scheduler.enqueue(
                _pod(base * 10_000 + ti * 1_000 + j, f"p{base}-{j}"))


def _binds(results):
    return {name: dict(r.assignments) for name, r in results.items()}


class TestQualityTenantAxis:
    def test_lp_tenants_join_batched_cycle(self, kit_off):
        """The PR 13 gap, closed: an all-lp fleet runs the BATCHED
        cycle (one vmapped lp_pack_assign dispatch), bit-identical to
        serial per-tenant execution."""
        modes = {"a": "lp", "b": "lp", "c": "lp"}
        serial = _front(kit_off, modes, batch_tenant_axis=False)
        batched = _front(kit_off, modes, batch_tenant_axis=True)
        for front in (serial, batched):
            _seed_front(front, base=1)
        r_ser = serial.schedule_cycle()
        r_bat = batched.schedule_cycle()
        assert batched.last_mode == "batched"
        for t in batched.tenants():
            assert t.scheduler.last_solve_path == "quality_lp_batched"
        for t in serial.tenants():
            assert t.scheduler.last_solve_path == "quality_lp"
        assert _binds(r_ser) == _binds(r_bat)

    def test_mixed_fleet_partitions_both_programs(self, kit_off):
        """Plain and lp tenants share one batched cycle: each group
        dispatches through ITS program, nobody falls back to the
        serialized pipeline, and every tenant's binds match serial."""
        modes = {"a": "off", "b": "lp", "c": "off", "d": "lp"}
        serial = _front(kit_off, modes, batch_tenant_axis=False)
        batched = _front(kit_off, modes, batch_tenant_axis=True)
        for front in (serial, batched):
            _seed_front(front, base=2)
        r_ser = serial.schedule_cycle()
        r_bat = batched.schedule_cycle()
        assert batched.last_mode == "batched"
        paths = {t.name: t.scheduler.last_solve_path
                 for t in batched.tenants()}
        assert paths == {"a": "tenant_batched",
                         "b": "quality_lp_batched",
                         "c": "tenant_batched",
                         "d": "quality_lp_batched"}
        assert _binds(r_ser) == _binds(r_bat)

    def test_auto_mode_unescalated_joins_plain_program(self, kit_off):
        """auto tenants whose latch is DOWN are plain-group members —
        they keep the select+pass1 program until slack escalates."""
        modes = {"a": "auto", "b": "auto"}
        batched = _front(kit_off, modes, batch_tenant_axis=True)
        _seed_front(batched, base=3)
        batched.schedule_cycle()
        assert batched.last_mode == "batched"
        for t in batched.tenants():
            assert t.scheduler.last_solve_path in (
                "tenant_batched", "quality_lp_batched")
            # the latch decides the group; unescalated == plain
            if not t.scheduler._quality_escalate:
                assert t.scheduler.last_solve_path == "tenant_batched"
