"""Network-topology gangs on the served scheduler: gang records over the
wire, the tree built from node labels, a round's plans charged one after
another, and gangs of whole GPUs.

Against the plain reference (``benchmarks/reference/topology.py``): on
seeded small fabrics (pods x racks x 2 nodes, which host sits in which rack
slot drawn from the seed) every gang the program binds sits on exactly the
nodes the reference plans on the same state, round after round.  Then the
cases one by one: two gangs that each fit one pod alone but not together,
a gang that loses a member giving back every device grant, the gang kind
over the v2 codec and a snapshot replay, the tree from labels against a
hand-built one, and the kind through the scheduler binary's socket.
"""

from __future__ import annotations

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.reference import topology as reference  # noqa: E402
from koordinator_tpu import metrics  # noqa: E402
from koordinator_tpu.api.resources import (  # noqa: E402
    NUM_RESOURCE_DIMS,
    ResourceDim,
)

R = NUM_RESOURCE_DIMS
POD_KEY, RACK_KEY = "example.com/pod", "example.com/rack"
#: layer index of each gather: 0 = the cluster, 1 = a pod, 2 = a rack
CLUSTER, POD, RACK = 0, 1, 2
GPUS, GPU_CORE, GPU_MEM = 8, 100, 81_920


def node_alloc() -> np.ndarray:
    alloc = np.zeros(R, np.int32)
    alloc[ResourceDim.CPU] = 192_000
    alloc[ResourceDim.MEMORY] = 2_097_152
    alloc[ResourceDim.GPU] = GPUS * GPU_CORE
    alloc[ResourceDim.GPU_MEMORY] = GPUS * GPU_MEM
    return alloc


def member_request() -> np.ndarray:
    """One member: a whole 8-GPU node's devices."""
    req = np.zeros(R, np.int32)
    req[ResourceDim.CPU] = 160_000
    req[ResourceDim.MEMORY] = 1_572_864
    req[ResourceDim.GPU] = GPUS * GPU_CORE
    req[ResourceDim.GPU_MEMORY] = GPUS * GPU_MEM
    return req


def no_threshold_config():
    import jax.numpy as jnp

    from koordinator_tpu.ops.assignment import ScoringConfig

    # nodes that report no usage: LoadAware filters nothing
    return ScoringConfig.default().replace(
        usage_thresholds=jnp.zeros(R, jnp.int32))


def devices(unhealthy: tuple = ()) -> dict:
    return {"gpu": [{"core": GPU_CORE, "memory": GPU_MEM, "group": m // 4,
                     "healthy": m not in unhealthy} for m in range(GPUS)]}


class Fabric:
    """A scheduler behind an in-process sync service (the binding the
    served binary uses), on ``pods`` x ``racks`` x 2 labelled nodes, and
    the client's books: what is bound where, round by round."""

    def __init__(self, seed: int, pods: int = 2, racks: int = 8,
                 unhealthy: dict | None = None):
        from koordinator_tpu.scheduler.device_manager import DeviceManager
        from koordinator_tpu.scheduler.scheduler import Scheduler
        from koordinator_tpu.scheduler.snapshot import ClusterSnapshot
        from koordinator_tpu.transport.deltasync import (
            SchedulerBinding,
            StateSyncService,
        )

        n = pods * racks * 2
        self.sched = Scheduler(
            ClusterSnapshot(capacity=n), config=no_threshold_config(),
            device_manager=DeviceManager(), gang_passes=1,
            topology_layers=[POD_KEY, RACK_KEY])
        self.svc = StateSyncService()
        self.svc.attach_binding(SchedulerBinding(self.sched))
        rng = np.random.default_rng(seed)
        slot = rng.permutation(n)   # host i sits in rack slot slot[i]
        self.tree = reference.Tree(4)
        self.names = [f"h{i}" for i in range(n)]
        self.pod_of, self.rack_of = {}, {}
        for i, name in enumerate(self.names):
            s = int(slot[i])
            pod, rack = f"pod{s // (2 * racks)}", f"rack{s // 2}"
            self.pod_of[name], self.rack_of[name] = pod, rack
            sick = (unhealthy or {}).get(name, ())
            self.svc.upsert_node(name, node_alloc(),
                                 labels={POD_KEY: pod, RACK_KEY: rack},
                                 devices=devices(sick))
            self.tree.add(self.sched.snapshot.node_index[name],
                          [pod, rack, name])
        self.tree.finish()
        self.alloc = np.stack([node_alloc()] * n).astype(np.int64)
        self.bound: dict[str, str] = {}
        self.members: dict[str, list[str]] = {}
        self.spec: dict[str, tuple] = {}

    def offer(self, gang: str, size: int, gather: int, mults=()) -> None:
        self.svc.upsert_gang(gang, size, {
            "desired_slots": size, "must_gather_layer": gather,
            "layer_multiples": list(mults)})
        self.members[gang] = [f"{gang}-m{k:03d}" for k in range(size)]
        self.spec[gang] = (size, gather, mults)
        for pod in self.members[gang]:
            self.svc.add_pod(pod, member_request(), priority=9_000,
                             gang=gang)

    def leave(self, gang: str) -> None:
        for pod in self.members.pop(gang):
            self.bound.pop(pod, None)
            self.svc.remove_pod(pod)

    def free(self) -> np.ndarray:
        free = self.alloc.copy()
        for node in self.bound.values():
            free[self.sched.snapshot.node_index[node]] -= member_request()
        return free

    def reference_round(self) -> dict[str, list[int]]:
        """The reference's plan of every pending gang, in gang order, on
        the books' state: gang -> planned node rows."""
        pending = sorted(g for g, pods in self.members.items()
                         if not any(p in self.bound for p in pods))
        gangs = []
        for g in pending:
            size, gather, mults = self.spec[g]
            gangs.append({"members": np.stack([member_request()] * size),
                          "desired": size, "gather": gather,
                          "mults": list(mults) + [1] * (4 - len(mults))})
        valid = np.ones(len(self.alloc), bool)
        plans = reference.plan_round(self.tree, self.free(), valid, gangs)
        return {g: sorted(r for r, c in plan for _ in range(c))
                for g, plan in zip(pending, plans)}

    def solve(self):
        res = self.sched.schedule_round()
        self.bound.update(res.assignments)
        return res

    def rows_of(self, gang: str) -> list[int]:
        index = self.sched.snapshot.node_index
        return sorted(index[self.bound[p]] for p in self.members[gang]
                      if p in self.bound)


def gather_faults(fab: Fabric, gang: str) -> int:
    """0 when the gang's nodes share one domain at its gather layer and
    hold a multiple of 2 members a rack where it asks for that."""
    size, gather, mults = fab.spec[gang]
    nodes = [fab.bound[p] for p in fab.members[gang] if p in fab.bound]
    if not nodes:
        return 0
    faults = 0
    if gather == POD:
        faults += len({fab.pod_of[n] for n in nodes}) != 1
    if gather == RACK:
        faults += len({fab.rack_of[n] for n in nodes}) != 1
    if len(mults) > RACK and mults[RACK] > 1:
        racks: dict[str, int] = {}
        for n in nodes:
            racks[fab.rack_of[n]] = racks.get(fab.rack_of[n], 0) + 1
        faults += sum(c % mults[RACK] != 0 for c in racks.values())
    return faults


#: a round's arrivals: (size in nodes, gather layer, layer multiples)
MIX = [(2, RACK, ()), (2, RACK, ()), (4, POD, (1, 1, 2)),
       (6, POD, (1, 1, 2)), (8, CLUSTER, (1, 1, 2))]


@pytest.mark.parametrize("seed", [3, 11, 2**31 + 7])
def test_seeded_fabric_binds_what_the_reference_plans(seed):
    fab = Fabric(seed)
    rng = np.random.default_rng(seed)
    serial = waited = 0
    for rnd in range(5):
        # gangs of earlier rounds leave at random; a new mix arrives
        for gang in list(fab.members):
            if rng.random() < 0.5:
                fab.leave(gang)
        for size, gather, mults in MIX:
            fab.offer(f"j{serial:04d}", size, gather, mults)
            serial += 1
        want = fab.reference_round()
        fab.solve()
        for gang, rows in want.items():
            assert fab.rows_of(gang) == rows, (rnd, gang)
            assert gather_faults(fab, gang) == 0, (rnd, gang)
        waited += sum(not rows for rows in want.values())
    # the mix overfills the fabric: what no domain holds waits whole, and
    # the reference agrees on which gangs those are
    assert waited > 0


def test_two_gangs_that_each_fit_one_pod_go_to_different_pods():
    """Each of the two gangs fits either pod alone and no pod holds both:
    planned against the same state they would both take the tighter pod,
    the second would lose members in the solve and back off whole.
    Charged in turn, the second is planned onto the other pod."""
    fab = Fabric(5)
    # pod 0 tighter: one of its racks is taken
    fab.offer("a-first", 2, RACK)
    fab.solve()
    taken_pod = fab.pod_of[fab.bound["a-first-m000"]]
    fab.offer("b-gang", 10, POD, (1, 1, 2))
    fab.offer("c-gang", 10, POD, (1, 1, 2))
    want = fab.reference_round()
    before = metrics.topology_plans.value(labels={"outcome": "planned"})
    res = fab.solve()
    assert set(res.assignments) == set(fab.members["b-gang"]
                                       + fab.members["c-gang"])
    pods = [{fab.pod_of[fab.bound[p]] for p in fab.members[g]}
            for g in ("b-gang", "c-gang")]
    assert all(len(p) == 1 for p in pods)
    assert pods[0] != pods[1]
    assert pods[0] == {taken_pod}   # the tighter fit went first
    for gang, rows in want.items():
        assert fab.rows_of(gang) == rows
    assert metrics.topology_plans.value(
        labels={"outcome": "planned"}) - before == 2


def test_plans_in_turn_on_a_tree_given_at_construction():
    """The same conflict on a scheduler handed its tree (no labels, no
    wire): 2 pods x 8 racks x 2 nodes, one rack of pod 0 taken, two
    pod-gathered gangs of 10 nodes.  Planned against the same state both
    would take pod 0 and the second would back off; in turn, all 20 bind,
    one gang a pod."""
    from koordinator_tpu.api.resources import resource_vector
    from koordinator_tpu.ops.network_topology import (
        TopologyRequirements,
        TopologyTree,
    )
    from koordinator_tpu.scheduler import ClusterSnapshot, NodeSpec, PodSpec
    from koordinator_tpu.scheduler.scheduler import GangRecord, Scheduler

    tree = TopologyTree(["pod", "rack", "node"])
    snap = ClusterSnapshot(capacity=32)
    for i in range(32):
        tree.add_node([f"p{i // 16}", f"r{i // 2}", f"n{i}"])
        snap.upsert_node(NodeSpec(
            name=f"n{i}", allocatable=resource_vector(cpu=8_000, memory=16_384),
            usage=np.zeros(R, np.int32)))
    sched = Scheduler(snap, config=no_threshold_config(),
                      topology_tree=tree.build(capacity=32), gang_passes=1)

    def gang(name, size, layer, mults=()):
        sched.register_gang(GangRecord(
            name=name, min_member=size, topology=TopologyRequirements(
                desired_slots=size, must_gather_layer=layer,
                layer_multiples=mults)))
        for k in range(size):
            sched.enqueue(PodSpec(
                name=f"{name}-{k}", gang=name,
                requests=resource_vector(cpu=8_000, memory=1_024)))

    gang("a", 2, RACK)
    assert len(sched.schedule_round().assignments) == 2
    gang("b", 10, POD, (1, 1, 2))
    gang("c", 10, POD, (1, 1, 2))
    res = sched.schedule_round()
    assert len(res.assignments) == 20
    pods = {g: {int(res.assignments[f"{g}-{k}"][1:]) // 16
                for k in range(10)} for g in ("b", "c")}
    assert pods["b"] == {0} and pods["c"] == {1}


def test_a_gang_without_a_plan_backs_off_whole_and_is_counted():
    fab = Fabric(9, pods=2, racks=2)
    # a pod holds 4 nodes: a pod-gathered gang of 6 fits no pod
    fab.offer("big", 6, POD)
    before = metrics.topology_plans.value(labels={"outcome": "no_fit"})
    res = fab.solve()
    assert not res.assignments
    assert fab.reference_round() == {"big": []}
    assert metrics.topology_plans.value(
        labels={"outcome": "no_fit"}) - before == 1
    assert set(fab.sched.pending) == set(fab.members["big"])


def test_a_gang_that_loses_a_member_gives_back_every_device_grant():
    """The plan reads the node's aggregate rows, which hold 8 GPUs where
    one device is unhealthy; the device stage refuses that member, the
    gang misses its min-member, and its other member's grant goes back
    with it: nothing bound, every device free."""
    fab = Fabric(1, pods=1, racks=1, unhealthy={"h0": (3,)})
    fab.offer("g", 2, RACK)
    res = fab.solve()
    assert not res.assignments
    assert set(fab.sched.pending) == set(fab.members["g"])
    assert not any((fab.sched.resource_status.get(p) or {})
                   .get("device-allocated") for p in fab.members["g"])
    snap = fab.sched.snapshot
    snap.flush()
    plane = snap.state.devices
    free, total = np.asarray(plane.free), np.asarray(plane.total)
    for name in fab.names:
        row = snap.node_index[name]
        assert np.array_equal(free[row], total[row]), name
    # with the device healthy again the same gang binds, with its grants
    fab.svc.update_node_devices("h0", devices())
    res = fab.solve()
    assert set(res.assignments) == set(fab.members["g"])
    for pod in fab.members["g"]:
        grant = fab.sched.resource_status[pod]["device-allocated"]["gpu"]
        assert sorted(int(g["minor"]) for g in grant) == list(range(GPUS))


def test_gang_kind_round_trips_v2_and_replays_before_its_members():
    from koordinator_tpu.transport import deltasync as ds

    svc = ds.StateSyncService()
    svc.upsert_node("h0", node_alloc(), labels={POD_KEY: "p0"})
    svc.upsert_gang("g", 2, {"desired_slots": 2, "must_gather_layer": 2,
                             "layer_multiples": [1, 1, 2]})
    svc.upsert_gang("plain", 3)
    svc.add_pod("g-0", member_request(), gang="g")
    events = svc.log.since(0)
    doc, arrays = ds._pack_events_v2(events)
    back = ds._unpack_events_v2(doc, arrays)
    for (rv, event, _), entry in zip(events, back):
        assert {k: v for k, v in entry.items()
                if not k.startswith("__row_")} == dict(event, rv=rv)
    # the gang kind carries no array and defaults its topology to none
    plain = [e for e in back if e["name"] == "plain"][0]
    assert plain["topology"] is None and plain["min_member"] == 3

    # a fresh client replays the snapshot: records before pods
    snap_doc, snap_arrays = svc._snapshot(ds._pack_events_v2)
    kinds = [e["kind"] for e in ds._decode_events(snap_doc, snap_arrays)]
    assert kinds.index(ds.GANG) < kinds.index(ds.POD_ADD)
    from koordinator_tpu.scheduler.scheduler import Scheduler
    from koordinator_tpu.scheduler.snapshot import ClusterSnapshot

    sched = Scheduler(ClusterSnapshot(capacity=4))
    binding = ds.SchedulerBinding(sched)
    arrays_of = ds._unpack_event_arrays
    ds._dispatch_events(binding, [
        (e, arrays_of(e, snap_arrays))
        for e in ds._decode_events(snap_doc, snap_arrays)])
    rec = sched.gangs["g"]
    assert rec.min_member == 2
    assert rec.topology.must_gather_layer == 2
    assert rec.topology.layer_multiples == (1, 1, 2)
    assert sched.gangs["plain"].topology is None
    assert "g-0" in sched.pending
    # the debug surface shows what arrived
    from koordinator_tpu.scheduler.services import DebugService

    status, body = DebugService(sched).handle("/apis/v1/gangs")
    assert status == 200, body
    shown = {g["name"]: g["topology"] for g in body}
    assert shown["plain"] is None
    assert shown["g"] == {"desired_slots": 2, "must_gather_layer": 2,
                          "layer_multiples": (1, 1, 2)}


@pytest.mark.parametrize("bad", [
    {"min_member": 0},
    {"min_member": 2, "topology": {"desired_slots": "2"}},
    {"min_member": 2, "topology": {"layer_multiples": [0]}},
    {"min_member": 2, "topology": {"spread": 1}},
])
def test_a_malformed_gang_push_commits_nothing(bad):
    from koordinator_tpu.transport import deltasync as ds
    from koordinator_tpu.transport import wire

    svc = ds.StateSyncService()
    with pytest.raises(wire.WireSchemaError):
        svc._handle_state_push({"kind": "gang", "name": "g", **bad}, {})
    assert svc.rv == 0 and not svc.gangs


def test_tree_from_labels_matches_a_hand_built_tree_and_grows():
    from koordinator_tpu.ops.network_topology import TopologyTree

    fab = Fabric(4, pods=2, racks=3)
    sched = fab.sched
    with sched.lock:
        got = sched._current_topology()
    hand = TopologyTree([POD_KEY, RACK_KEY, "node"])
    for name in fab.names:
        hand.set_node(sched.snapshot.node_index[name],
                      [fab.pod_of[name], fab.rack_of[name], name])
    want = hand.build(capacity=sched.snapshot.capacity)
    for field in ("topo_layer", "topo_parent", "node_path", "node_topo",
                  "topo_to_node"):
        assert np.array_equal(np.asarray(getattr(got, field)),
                              np.asarray(getattr(want, field))), field
    assert got.num_topo == 1 + 2 + 6 + 12
    assert metrics.topology_tree_nodes.value() == got.num_topo
    # the tree is not rebuilt while nothing about the nodes changes
    with sched.lock:
        assert sched._current_topology() is got
    # a node joins with labels, one with none joins at the root; the ids
    # there are kept and the new ones come after them
    fab.svc.upsert_node("late", node_alloc(),
                        labels={POD_KEY: "pod1", RACK_KEY: "rack9"})
    fab.svc.upsert_node("bare", node_alloc())
    with sched.lock:
        grown = sched._current_topology()
    old_t = got.num_topo
    assert grown.num_topo == old_t + 3   # rack9, late, bare
    assert np.array_equal(np.asarray(grown.topo_parent)[:old_t],
                          np.asarray(got.topo_parent))
    bare = sched.snapshot.node_index["bare"]
    leaf = int(np.asarray(grown.node_topo)[bare])
    assert int(np.asarray(grown.topo_parent)[leaf]) == 0
    assert int(np.asarray(grown.topo_layer)[leaf]) == 3
    assert grown.node_path.shape[0] == sched.snapshot.capacity


def test_gangs_and_labels_cross_the_scheduler_binary_socket(tmp_path):
    """The served binary: node labels, the gang records and the members
    all arrive as STATE_PUSH frames on its listen socket, and one solve
    request binds the gathered gang."""
    from koordinator_tpu.cmd.binaries import MAINS
    from koordinator_tpu.transport import RpcClient
    from koordinator_tpu.transport.services import solve_remote
    from koordinator_tpu.transport.wire import FrameType

    cwd = os.getcwd()
    os.chdir(tmp_path)
    assembled = MAINS["koord-scheduler"]([
        "--disable-leader-election", "--node-capacity", "8",
        "--listen-socket", "s.sock", "--gang-passes", "1",
        "--topology-layer-labels", f"{POD_KEY},{RACK_KEY}"])
    client = RpcClient("s.sock", timeout=600.0)
    try:
        client.connect()
        small = np.zeros(R, np.int32)
        small[ResourceDim.CPU], small[ResourceDim.MEMORY] = 8_000, 16_384
        for i in range(8):
            client.call(FrameType.STATE_PUSH, {
                "kind": "node_upsert", "name": f"h{i}",
                "labels": {POD_KEY: f"pod{i // 4}",
                           RACK_KEY: f"rack{i // 2}"}},
                {"allocatable": small})
        client.call(FrameType.STATE_PUSH, {
            "kind": "gang", "name": "job", "min_member": 4,
            "topology": {"desired_slots": 4, "must_gather_layer": POD,
                         "layer_multiples": [1, 1, 2]}})
        ask = np.zeros(R, np.int32)
        # more than half a node's memory: one member a node
        ask[ResourceDim.CPU], ask[ResourceDim.MEMORY] = 4_000, 12_000
        for k in range(4):
            client.call(FrameType.STATE_PUSH, {
                "kind": "pod_add", "name": f"job-{k}", "gang": "job"},
                {"requests": ask})
        doc = solve_remote(client)
        nodes = set(doc["assignments"].values())
        assert len(doc["assignments"]) == 4
        # one pod of four nodes, two members a rack: all four nodes of it
        assert nodes in ({"h0", "h1", "h2", "h3"}, {"h4", "h5", "h6", "h7"})
        assert assembled.component.gangs["job"].topology.desired_slots == 4
    finally:
        client.close()
        assembled.stop()
        os.chdir(cwd)
