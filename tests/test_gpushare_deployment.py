"""DeviceShare inside the batched solve: a pod that asks for a device is
bound only together with a grant that satisfies it.

Against the plain reference (``benchmarks/reference/deviceshare.py``): with
batches of one pod the node set the program finds feasible and the minors
it grants are the reference's, on seeded fragmented states; with batches of
hundreds the guarantees of the ``gpushare-1k`` configuration read 0 over a
dozen cycles of arrivals and departures through the scheduler binary on a
real socket.  Then the cases one by one: the placement the parent got wrong
(aggregate fits, no device does), two pods racing for one free device in
one round, a device turning unhealthy and back under running pods, a
release that makes a waiting whole-GPU pod fit, the commit-time grant of a
path without the device stage, and the spans and counters.
"""

from __future__ import annotations

import functools
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.reference import deviceshare as reference  # noqa: E402
from benchmarks.spans import Spans  # noqa: E402
from koordinator_tpu import metrics, timeline  # noqa: E402
from koordinator_tpu.api.resources import (  # noqa: E402
    NUM_RESOURCE_DIMS,
    ResourceDim,
)
from koordinator_tpu.ops import deviceshare as ds  # noqa: E402
from koordinator_tpu.ops.assignment import (  # noqa: E402
    ScoringConfig,
    greedy_assign,
    score_pods,
)
from koordinator_tpu.ops.batch_assign import batch_assign  # noqa: E402
from koordinator_tpu.scheduler.device_manager import DeviceManager  # noqa: E402
from koordinator_tpu.scheduler.scheduler import (  # noqa: E402
    BoundPod,
    Scheduler,
    SchedulingResult,
)
from koordinator_tpu.scheduler.snapshot import (  # noqa: E402
    ClusterSnapshot,
    NodeSpec,
    PodSpec,
)
from koordinator_tpu.state.cluster_state import (  # noqa: E402
    ClusterState,
    PodBatch,
)
from koordinator_tpu.transport.deltasync import SchedulerBinding  # noqa: E402

NODES, SLOTS, MEMORY = 33, 8, 81_920
#: (core, gpu-memory) asks: shares, one GPU, whole GPUs, one that fits no
#: device though the aggregate rows hold it
ASKS = {"share10": (10, 8_192), "share25": (25, 20_480),
        "share50": (50, 40_960), "gpu1": (100, 81_920),
        "gpu2": (200, 163_840), "gpu4": (400, 327_680),
        "gpu8": (800, 655_360), "too_wide": (100, 90_000)}


@functools.cache
def jitted():
    """(greedy, batch, score), jitted once for the module's cases; jax is
    imported here, not at collection."""
    import jax

    return (jax.jit(greedy_assign, static_argnames=("with_grants",)),
            jax.jit(batch_assign, static_argnames=("with_grants", "method")),
            jax.jit(score_pods))


def device_plane(table, capacity: int):
    """The reference's table as the program's ``DeviceState``, padded to a
    state's node capacity."""
    import jax.numpy as jnp

    pad = capacity - table.valid.shape[0]

    def padded(a):
        return jnp.asarray(np.concatenate(
            [a, np.zeros((pad,) + a.shape[1:], a.dtype)]).astype(
                bool if a.dtype == bool else np.int32))

    return ds.DeviceState(
        total=padded(table.total), free=padded(table.free),
        valid=padded(table.valid), healthy=padded(table.healthy),
        group=padded(table.group))


def fragmented(seed: int):
    """A seeded cluster of 32 nodes x 8 GPUs and one of 4, fragmented by
    random shared and whole grants and a few unhealthy devices: (the
    reference's table, the program's state with the same device plane)."""
    rng = np.random.default_rng(seed)
    table = reference.Table(NODES, SLOTS)
    for node in range(NODES):
        count = 4 if node == NODES - 1 else SLOTS
        table.set_inventory(node, [
            {"core": 100, "memory": MEMORY, "group": m // 4,
             "healthy": rng.random() > 0.05} for m in range(count)])
    for _ in range(int(rng.integers(60, 160))):
        core, memory = ASKS[str(rng.choice(list(ASKS)[:7]))]
        table.reserve(int(rng.integers(NODES)), core, memory)
    alloc = np.zeros((NODES, NUM_RESOURCE_DIMS), np.int32)
    alloc[:, ResourceDim.CPU] = 128_000
    alloc[:, ResourceDim.MEMORY] = 1_048_576
    alloc[:, ResourceDim.GPU] = table.valid.sum(axis=1) * 100
    alloc[:, ResourceDim.GPU_MEMORY] = table.valid.sum(axis=1) * MEMORY
    requested = np.zeros_like(alloc)
    held = (table.total - table.free).sum(axis=1)
    requested[:, ResourceDim.GPU] = held[:, 0]
    requested[:, ResourceDim.GPU_MEMORY] = held[:, 1]
    state = ClusterState.from_arrays(alloc, requested=requested)
    return table, state.replace(devices=device_plane(table, state.capacity))


def one_pod(core: int, memory: int) -> PodBatch:
    request = np.zeros((1, NUM_RESOURCE_DIMS), np.int32)
    request[0, ResourceDim.CPU] = 1_000
    request[0, ResourceDim.GPU] = core
    request[0, ResourceDim.GPU_MEMORY] = memory
    return PodBatch.build(request, class_capacity=8, capacity=16)


@pytest.mark.parametrize("ask", list(ASKS))
@pytest.mark.parametrize("seed", [11, 12, 13, 14])
def test_one_pod_filter_and_grant_equal_the_reference(seed, ask):
    table, state = fragmented(seed)
    core, memory = ASKS[ask]
    pods = one_pod(core, memory)
    cfg = ScoringConfig.default()
    # Filter: the node set (the aggregate rows agree with the devices
    # here, so the device filter is what decides)
    _greedy, _, _score = jitted()
    _, feasible = _score(state, pods, cfg)
    want = table.filter(core, memory)
    aggregate = np.all(
        (np.asarray(pods.requests[0])[None, :]
         <= np.asarray(state.free)[:NODES])
        | (np.asarray(pods.requests[0])[None, :] == 0), axis=1)
    assert np.array_equal(np.asarray(feasible[0])[:NODES], want & aggregate)
    # Reserve: the minors, on the node the solve chose
    a, new_state, _, grants, _ = _greedy(state, pods, cfg, with_grants=True)
    node = int(a[0])
    if not (want & aggregate).any():
        assert node == -1 and not np.asarray(grants.selection[0]).any()
        return
    assert (want & aggregate)[node]
    minors = table.reserve(node, core, memory)
    assert np.flatnonzero(np.asarray(grants.selection[0])).tolist() == minors
    assert np.array_equal(np.asarray(new_state.devices.free)[:NODES],
                          table.free)


@pytest.mark.parametrize("seed", [31, 32, 33, 34])
def test_the_four_writings_of_the_reserve_rule_agree(seed):
    """Reserve is written four times: the reference's loops, the solve's
    batched ``grant_rows``, the books' ``DeviceTable.grant`` (the
    commit-time grant) and the single-node ``allocate_on_node`` kernel the
    joint GPU + NIC path keeps.  On every node of a fragmented cluster and
    every ask they pick the same minors."""
    import jax.numpy as jnp

    from koordinator_tpu.scheduler.device_manager import DeviceTable

    table, state = fragmented(seed)
    dev = state.devices
    books = DeviceTable(NODES, SLOTS)
    books.total[:], books.free[:] = table.total, table.free
    books.valid[:], books.healthy[:] = table.valid, table.healthy
    books.group[:] = table.group
    rows = jnp.arange(NODES)
    usable = (dev.valid & dev.healthy)[rows]
    for core, memory in ASKS.values():
        request = np.zeros((NODES, NUM_RESOURCE_DIMS), np.int32)
        request[:, ResourceDim.GPU] = core
        request[:, ResourceDim.GPU_MEMORY] = memory
        req = ds.pod_device_requests(jnp.asarray(request))
        sel, ok = ds.grant_rows(dev.free[rows], dev.total[rows], usable,
                                dev.group[rows], req)
        n_whole, per_core, per_mem = reference.split_request(core, memory)
        for node in range(NODES):
            before = table.free[node].copy()
            want = table.reserve(node, core, memory)
            table.free[node] = before          # every node from one state
            got = np.flatnonzero(np.asarray(sel[node])).tolist()
            assert (got or None) == want and bool(ok[node]) == (want is not None)
            assert books.grant(node, core, memory) == want
            one, one_ok = ds.allocate_on_node(
                dev, jnp.int32(node), jnp.int32(n_whole),
                jnp.int32(per_core), jnp.int32(per_mem))
            assert (np.flatnonzero(np.asarray(one)).tolist() or None) == want
            assert bool(one_ok) == (want is not None)


@pytest.mark.parametrize("engine", ["greedy", "batch"])
@pytest.mark.parametrize("seed", [21, 22, 23])
def test_a_batch_of_hundreds_grants_nothing_twice(seed, engine):
    """A whole batch in one solve: every assigned device pod holds a grant
    Reserve could have made, no device is granted past its total, and the
    free tensor that comes back is the one that went in less the grants."""
    table, state = fragmented(seed)
    rng = np.random.default_rng(seed)
    p = 300
    request = np.zeros((p, NUM_RESOURCE_DIMS), np.int32)
    request[:, ResourceDim.CPU] = 1_000
    names = list(ASKS)
    asks = [ASKS[names[i]] for i in rng.integers(len(names), size=p)]
    plain = rng.random(p) < 0.2
    for i, (core, memory) in enumerate(asks):
        if not plain[i]:
            request[i, ResourceDim.GPU] = core
            request[i, ResourceDim.GPU_MEMORY] = memory
    pods = PodBatch.build(request, class_capacity=8,
                          priority=rng.integers(9_000, 10_000, p))
    solve = jitted()[0 if engine == "greedy" else 1]
    # the greedy engine appends its scan stats after the grants
    a, new_state, _, grants, *_ = solve(state, pods, ScoringConfig.default(),
                                        with_grants=True)
    a, sel = np.asarray(a)[:p], np.asarray(grants.selection)[:p]
    before = table.free.copy()
    bound = 0
    for i in np.argsort(-np.asarray(pods.priority)[:p], kind="stable"):
        core, memory = (int(request[i, ResourceDim.GPU]),
                        int(request[i, ResourceDim.GPU_MEMORY]))
        minors = np.flatnonzero(sel[i]).tolist()
        if a[i] < 0 or core == 0:
            assert not minors
            continue
        _, per_core, per_mem = reference.split_request(core, memory)
        assert not reference.grant_faults(table, int(a[i]), minors, core,
                                          memory, per_core, per_mem)
        for m in minors:
            table.free[a[i], m] -= (per_core, per_mem)
        bound += 1
    assert bound > 20 and (table.free >= 0).all()
    assert np.array_equal(np.asarray(new_state.devices.free)[:NODES],
                          table.free)
    assert (table.free != before).any()
    # the pods that ask what no device holds stay unassigned
    too_wide = [i for i, ask in enumerate(asks)
                if ask == ASKS["too_wide"] and not plain[i]]
    assert too_wide and (a[too_wide] == -1).all()


def test_two_pods_race_for_one_free_device_in_one_round():
    """One node, one wholly free device left, two pods that each ask for a
    whole GPU: the aggregate rows would hold both (another device is half
    used), the round accepts both, the device stage grants the one ahead
    in priority and undoes the other, which ends unassigned."""
    table = reference.Table(1, SLOTS)
    table.set_inventory(0, [{"core": 100, "memory": MEMORY, "group": 0}
                            for _ in range(2)])
    assert table.reserve(0, 50, 40_960) == [0]
    alloc = np.zeros((1, NUM_RESOURCE_DIMS), np.int32)
    alloc[0, [ResourceDim.CPU, ResourceDim.GPU, ResourceDim.GPU_MEMORY]] = (
        64_000, 300, 3 * MEMORY)      # the aggregate rows say: room for both
    requested = np.zeros_like(alloc)
    requested[0, [ResourceDim.GPU, ResourceDim.GPU_MEMORY]] = (50, 40_960)
    state = ClusterState.from_arrays(alloc, requested=requested)
    state = state.replace(devices=device_plane(table, state.capacity))
    request = np.zeros((2, NUM_RESOURCE_DIMS), np.int32)
    request[:, ResourceDim.CPU] = 1_000
    request[:, ResourceDim.GPU] = 100
    request[:, ResourceDim.GPU_MEMORY] = MEMORY
    pods = PodBatch.build(request, priority=np.array([9_100, 9_900]),
                          class_capacity=8)
    a, new_state, _, grants = jitted()[1](
        state, pods, ScoringConfig.default(), with_grants=True)
    a = np.asarray(a)
    assert a[1] == 0 and a[0] == -1
    assert np.flatnonzero(np.asarray(grants.selection[1])).tolist() == [1]
    assert not np.asarray(grants.selection[0]).any()
    assert int(np.asarray(grants.lost_races)[0]) == 1
    # the loser was not charged: one GPU and a half are requested
    assert int(new_state.node_requested[0, ResourceDim.GPU]) == 150
    assert np.asarray(new_state.devices.free)[0, :2, 0].tolist() == [50, 0]


# -- the scheduler ------------------------------------------------------------


def gpu_node(name: str, gpus: int = 8) -> NodeSpec:
    alloc = np.zeros(NUM_RESOURCE_DIMS, np.int32)
    alloc[[ResourceDim.CPU, ResourceDim.MEMORY, ResourceDim.GPU,
           ResourceDim.GPU_MEMORY]] = (128_000, 1_048_576, gpus * 100,
                                       gpus * MEMORY)
    return NodeSpec(name=name, allocatable=alloc)


def inventory(gpus: int = 8, unhealthy=()) -> list[dict]:
    return [{"core": 100, "memory": MEMORY, "group": m // 4,
             "healthy": m not in unhealthy} for m in range(gpus)]


def gpu_pod(name: str, core: int, memory: int | None = None,
            priority: int = 9_500) -> PodSpec:
    request = np.zeros(NUM_RESOURCE_DIMS, np.int32)
    request[[ResourceDim.CPU, ResourceDim.MEMORY]] = (1_000, 1_024)
    request[ResourceDim.GPU] = core
    request[ResourceDim.GPU_MEMORY] = (MEMORY * core // 100
                                       if memory is None else memory)
    return PodSpec(name=name, requests=request, priority=priority)


def mk_scheduler(nodes: dict[str, list[dict]], **kw):
    snap = ClusterSnapshot(capacity=16)
    dm = DeviceManager()
    sched = Scheduler(snap, device_manager=dm, **kw)
    for name, devices in nodes.items():
        snap.upsert_node(gpu_node(name, len(devices)))
        dm.register_node_devices("gpu", name, devices)
    return sched, dm


def minors_of(sched, pod: str) -> list[int]:
    return sorted(g["minor"] for g in
                  sched.resource_status[pod]["device-allocated"]["gpu"])


def test_aggregate_fits_no_device_does_the_pod_stays_pending():
    """The case the parent got wrong: eight GPUs each half used leave 400
    on the aggregate row, and a pod that asks for one whole GPU was bound
    there with no device.  It stays pending, diagnosed by the device."""
    sched, _ = mk_scheduler({"n0": inventory()})
    for m in range(8):
        # one half on every device (binpack would fill four and leave four
        # free): replayed, as a restarted scheduler finds them
        sched.add_bound_pod(
            BoundPod(name=f"half{m}", node="n0",
                     requests=gpu_pod("x", 50).requests, priority=9_000),
            resource_status={"device-allocated": {"gpu": [
                {"minor": m, "resources": {"core": 50,
                                           "memory": MEMORY // 2}}]}})
    sched.enqueue(gpu_pod("whole", 100))
    sched.enqueue(gpu_pod("half", 50))
    res = sched.schedule_round()
    assert res.assignments == {"half": "n0"}
    assert "whole" in sched.pending and "whole" not in sched.bound
    diag = res.failures["whole"]
    assert diag.device_unfit == 1 and diag.reason_counts["device_fit"] == 1
    assert "insufficient devices (gpu)" in diag.message()
    assert "whole" not in sched.resource_status
    # the aggregate row was not charged for it
    row = sched.snapshot.node_index["n0"]
    assert int(sched.snapshot.state.node_requested[row, ResourceDim.GPU]) == 450


def test_a_release_makes_a_waiting_whole_gpu_pod_fit_next_round():
    sched, dm = mk_scheduler({"n0": inventory(2)})
    sched.enqueue(gpu_pod("a", 50))
    sched.enqueue(gpu_pod("b", 100))
    assert set(sched.schedule_round().assignments) == {"a", "b"}
    sched.enqueue(gpu_pod("waits", 100))
    res = sched.schedule_round()
    assert "waits" in res.failures and "gpu" in res.failures["waits"].message()
    sched.delete_pod("b")
    res = sched.schedule_round()
    assert res.assignments == {"waits": "n0"}
    assert len(minors_of(sched, "waits")) == 1
    state = sched.snapshot.state
    row = sched.snapshot.node_index["n0"]
    # the device-resident plane: one half and one whole device taken
    assert sorted(np.asarray(state.devices.free)[row, :2, 0].tolist()) == [0, 50]
    assert np.array_equal(np.asarray(state.devices.free),
                          dm._tables["gpu"].free)


def test_a_device_turning_unhealthy_and_back_keeps_grants_and_other_nodes():
    sched, dm = mk_scheduler({"n0": inventory(), "n1": inventory()})
    for i in range(6):
        sched.enqueue(gpu_pod(f"p{i}", 200))
    assert len(sched.schedule_round().assignments) == 6
    held = {p: (sched.bound[p].node, minors_of(sched, p)) for p in sched.bound}
    on_n0 = [p for p, (node, _) in held.items() if node == "n0"]
    sick = held[on_n0[0]][1][0]
    other = sched.snapshot.node_index["n1"]
    before = np.asarray(sched.snapshot.state.devices.free)[other].copy()
    events = metrics.deviceshare_inventory_events.value()
    total_buffer = dm._tables["gpu"].total
    binding = SchedulerBinding(sched)
    binding.node_devices(
        {"name": "n0", "devices": {"gpu": inventory(unhealthy={sick})}})
    assert metrics.deviceshare_inventory_events.value() == events + 1
    sched.snapshot.flush()
    state = sched.snapshot.state
    row = sched.snapshot.node_index["n0"]
    assert not bool(state.devices.healthy[row, sick])
    # the grants stand, the other node's row did not move
    assert {p: (sched.bound[p].node, minors_of(sched, p))
            for p in sched.bound} == held
    assert np.array_equal(np.asarray(state.devices.free)[other], before)
    assert dm._tables["gpu"].total is total_buffer     # nothing rebuilt
    # a new pod cannot be given the sick device, whoever leaves
    sched.delete_pod(on_n0[0])
    sched.enqueue(gpu_pod("new", 200))
    res = sched.schedule_round()
    assert "new" in res.assignments
    assert res.assignments["new"] != "n0" or sick not in minors_of(sched, "new")
    binding.node_devices(
        {"name": "n0", "devices": {"gpu": inventory()}})
    sched.snapshot.flush()
    assert bool(sched.snapshot.state.devices.healthy[row, sick])
    assert np.array_equal(np.asarray(sched.snapshot.state.devices.free),
                          dm._tables["gpu"].free)


def test_a_path_without_the_device_stage_grants_at_the_commit_or_unbinds():
    """The LP packing solve knows no device: its binds take their grant
    from the host books at the commit, and one that finds none is
    unreserved and pending again, diagnosed; it never binds without."""
    sched, dm = mk_scheduler({"n0": inventory(2)}, quality_mode="lp",
                             batch_solver_threshold=1)
    sched.enqueue(gpu_pod("a", 100, priority=9_900))
    res = sched.schedule_round()
    assert sched.last_solve_path == "quality_lp"
    assert res.assignments == {"a": "n0"} and len(minors_of(sched, "a")) == 1
    sched.snapshot.flush()
    assert np.array_equal(np.asarray(sched.snapshot.state.devices.free),
                          dm._tables["gpu"].free)
    # make the books refuse what the LP solve (aggregate rows only, the
    # device filter inside its feasibility aside) may still place: take
    # the last device behind its back
    assert dm.allocate("gpu", "n0", "intruder", 100) is not None
    pod = gpu_pod("late", 100)
    result = SchedulingResult({}, {}, 0)
    with sched.lock:
        sched.snapshot.reserve("n0", pod.requests)
        sched._commit_bind(pod, "n0", result)
    assert "late" not in sched.bound and "late" in sched.pending
    assert "late" not in result.assignments
    assert "insufficient devices (gpu)" in result.failures["late"].message()
    row = sched.snapshot.node_index["n0"]
    assert int(sched.snapshot.state.node_requested[row, ResourceDim.GPU]) == 100


def test_the_device_plane_follows_the_snapshot_as_it_grows_and_shrinks():
    """Rows are the snapshot's: the books grow with its capacity, a removed
    node's row is cleared for its next tenant, and the plane goes back to
    None (the programs of a cluster without devices) with the last
    inventory."""
    sched, dm = mk_scheduler({f"n{i}": inventory() for i in range(12)})
    sched.enqueue(gpu_pod("a", 800))
    assert sched.schedule_round().assignments
    home = sched.bound["a"].node
    for i in range(12, 40):                      # 16 rows -> 64
        sched.snapshot.upsert_node(gpu_node(f"n{i}"))
        dm.register_node_devices("gpu", f"n{i}", inventory())
    sched.snapshot.flush()
    state = sched.snapshot.state
    assert state.capacity == 64 and state.devices.shape == (64, 8)
    assert np.array_equal(np.asarray(state.devices.free),
                          dm._tables["gpu"].free)
    row = sched.snapshot.node_index[home]
    assert not np.asarray(state.devices.free)[row].any()      # a holds it
    # a node leaves: its row is empty for whoever takes it next
    binding = SchedulerBinding(sched)
    gone = next(n for n in sched.snapshot.node_index if n != home)
    gone_row = sched.snapshot.node_index[gone]
    binding.node_remove(gone)
    sched.snapshot.upsert_node(gpu_node("fresh", 0))          # no devices
    sched.snapshot.flush()
    assert sched.snapshot.node_index["fresh"] == gone_row
    assert not np.asarray(sched.snapshot.state.devices.valid)[gone_row].any()
    sched.enqueue(gpu_pod("b", 100))
    assert sched.schedule_round().assignments["b"] != "fresh"
    for name in list(sched.snapshot.node_index):
        binding.node_remove(name)
    sched.snapshot.flush()
    assert sched.snapshot.state.devices is None


def test_on_a_mesh_a_cluster_with_devices_runs_the_single_device_program():
    """The kit keeps a state with devices off the ``shard_map`` twins (they
    carry no device stage): the single-device programs run, placed by
    GSPMD over the node-sharded state, the grants come out of the solve,
    and the recompile label carries no ``@Nshard`` suffix."""
    snap = ClusterSnapshot(capacity=1024)
    dm = DeviceManager()
    sched = Scheduler(snap, device_manager=dm, batch_solver_threshold=64)
    if not sched.kit.sharding_active_for(1024):
        pytest.skip("no solve mesh on this backend")
    for i in range(256):
        snap.upsert_node(gpu_node(f"n{i}"))
        dm.register_node_devices("gpu", f"n{i}", inventory())
    rng = np.random.default_rng(5)
    for i in range(200):
        sched.enqueue(gpu_pod(f"p{i}", int(rng.choice([25, 50, 100, 200, 400]))))
    before = {tuple(sorted(labels.items())) for labels, _
              in metrics.solver_recompiles.items()}
    res = sched.schedule_round()
    assert sched.last_solve_path == "full_cold" and len(res.assignments) == 200
    state = sched.snapshot.state
    assert "nodes" in str(state.devices.free.sharding.spec)
    assert np.array_equal(np.asarray(state.devices.free),
                          dm._tables["gpu"].free)
    assert all(minors_of(sched, pod) for pod in res.assignments)
    new = {dict(key)["shape"] for key in
           {tuple(sorted(labels.items())) for labels, _
            in metrics.solver_recompiles.items()} - before}
    assert new and not any("shard" in shape for shape in new), new


def test_a_commit_time_grant_sees_the_grants_the_solve_made_that_round():
    """One commit, two binds on one node: the solve granted minor 0 to the
    first, the second comes from a path without the device stage.  Its
    grant is taken from the books AFTER the solve's are written: minor 1,
    never the device the first holds."""
    sched, dm = mk_scheduler({"n0": inventory(2)})
    first, second = gpu_pod("first", 100), gpu_pod("second", 100)
    selections = np.zeros((2, 8), bool)
    selections[0, 0] = True
    with sched.lock:
        for pod in (first, second):
            sched.snapshot.reserve("n0", pod.requests)
        result = SchedulingResult({}, {}, 0)
        sched._commit_bind_batch([(first, "n0"), (second, "n0")], result,
                                 selections)
    assert set(result.assignments) == {"first", "second"}
    assert minors_of(sched, "first") == [0]
    assert minors_of(sched, "second") == [1]
    assert dm._tables["gpu"].free[sched.snapshot.node_index["n0"], :2, 0
                                  ].tolist() == [0, 0]


# -- the deployment, through the binary on a real socket ------------------------

CYCLES = 12


def small_config() -> tuple[dict, dict]:
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "gpushare-1k.json")) as f:
        config = json.load(f)
    # batches of a few hundred go through the batch engine's rounds
    config["scheduler_flags"] = [*config["scheduler_flags"],
                                 "--batch-solver-threshold", "128"]
    return config, {"nodes": 48, "wave_pods": 70, "standing": 24}


@pytest.fixture(scope="module")
def run(tmp_path_factory):
    from benchmarks.deployments import served_devices
    from benchmarks.kinds import device_closed as kind

    config, sizes = small_config()
    params = {"arrive": 70, "device_events": 2, "gpu_fill_target": 0.7,
              "fill_wave": 150, "fill_max_waves": 12,
              "warm_standing": [24, 64], "resettle": 1,
              "settle_window": 2,
              "settle_tolerance": 1.0, "settle_max": 2}
    cwd = os.getcwd()
    os.chdir(tmp_path_factory.mktemp("gpushare"))
    dep = served_devices.Deployment(config, sizes, 7, "run")
    spans = Spans(False)

    def outcomes():
        return {labels["outcome"]: value for labels, value
                in metrics.deviceshare_grants.items()}

    before = {"outcomes": outcomes(),
              "events": metrics.deviceshare_inventory_events.value()}
    try:
        kind.setup(dep, params, spans)
        dep.books.window_open = True
        import time as _time

        t_open = _time.perf_counter()
        cycles = [kind.cycle(dep, params, spans) for _ in range(CYCLES)]
        t_close = _time.perf_counter()
        docs = timeline.RECORDER.cycles(64)
        rounds = dep.flight_records(0)
        compared = dep.verify()
        reported = dep.reported()
        yield {"dep": dep, "cycles": cycles, "compared": compared,
               "reported": reported, "docs": docs, "rounds": rounds,
               "t_open": t_open, "t_close": t_close, "before": before,
               "after": {"outcomes": outcomes(),
                         "events":
                             metrics.deviceshare_inventory_events.value()}}
    finally:
        dep.close()
        os.chdir(cwd)


GUARANTEES = ["overcommit_cells", "undiagnosed_pods", "charge_mismatch_cells",
              "held_node_mismatch_cells", "held_pod_mismatch",
              "bind_without_grant", "grant_invalid",
              "device_overcommit_cells", "device_state_mismatch",
              "standing_bound", "undiagnosed", "missed_device_fit"]


@pytest.mark.parametrize("guarantee", GUARANTEES)
def test_every_guarantee_reads_zero_over_a_dozen_cycles(run, guarantee):
    assert run["compared"][guarantee] == 0, run["compared"]


def test_the_cycles_fragment_the_cluster_and_the_batch_engine_ran(run):
    cycles = run["cycles"]
    assert len(cycles) == CYCLES
    assert sum(c["bound"] for c in cycles) > 200
    assert sum(c["left"] for c in cycles) > 200
    assert sum(c["events"] for c in cycles) == 2 * CYCLES
    # jobs of every shape were bound with devices, and some waited in vain
    dep = run["dep"]
    shapes = {dep.shape_of[p] for p in dep.books.bound if p in dep.shape_of}
    assert {"share", "gpu1", "gpu2"} <= shapes
    assert 0.3 < run["reported"]["gpu_core_allocated_share"] <= 1.0
    window = [r for r in run["rounds"] if r["pods"] >= 128]
    assert len(window) >= CYCLES
    assert all(r["solver"] == "batch" for r in window[-CYCLES:])
    # the standing pods are there in every round, and never bound
    assert len(dep._standing_names & dep.books.pending) == 24


def test_the_spans_and_counters_carry_the_right_n(run):
    from benchmarks import program_spans

    class Ctx:
        timeline_docs = run["docs"]
        t_open, t_close = run["t_open"], run["t_close"]

    recs = program_spans.records(Ctx)
    cycles = run["cycles"]

    def members(name):
        return program_spans.total(recs, lambda r: r["name"] == name)[1]

    dep = run["dep"]
    granted_in_window = sum(
        1 for event in dep.events if event[0] == "bind" and event[5])
    # every grant was recorded under bind.devices, once a round
    assert 0 < members("bind.devices") <= granted_in_window
    grants = [r for r in recs if r["name"] == "bind.devices"]
    assert all(r["parent"] == "phase.Bind" for r in grants)
    assert len(grants) <= CYCLES
    # every departure of a device pod gave its devices back under
    # release.fine_grained
    releases = [r for r in recs if r["name"] == "release.devices"]
    assert releases and all(r["parent"] == "release.fine_grained"
                            for r in releases)
    assert 0 < members("release.devices") <= sum(c["left"] for c in cycles)
    assert round(members("sync.node_devices")) == 2 * CYCLES
    moved = {k: v - run["before"]["outcomes"].get(k, 0)
             for k, v in run["after"]["outcomes"].items()}
    assert moved["granted"] >= granted_in_window > 0
    assert moved["no_device"] > 0
    assert run["after"]["events"] - run["before"]["events"] >= 2 * CYCLES + 48
    assert metrics.deviceshare_whole_free_devices.value() >= 0
