"""NodeNUMAResource inside the batched solve: a pinned (LSR / LSE) pod is
bound only together with a cpuset the solve granted.

Against the plain reference (``benchmarks/reference/cpuset.py``), on seeded
fragmented clusters of 12 nodes of 16 or 32 CPUs in 2 NUMA nodes: the nodes
the program's Filter finds feasible for one pod are the reference's, and the
CPUs a batch is granted are the ones the reference's takes give when each
round's grants are replayed node by node, CPU for CPU, for FullPCPUs and
SpreadByPCPUs, on SingleNUMANode and unlabelled nodes, through the exact scan
and the propose/accept rounds.  Then the cases one by one: two pods racing
for a node's last core in one round, a release and a regrant through the
scheduler, a gang rolled back giving its cores back, the scan's entry filter
pruning a pod no NUMA node can hold, and the commit: no host take in a round
with the stage, an ungranted pinned pod pending, the span and the counter.
"""

from __future__ import annotations

import functools
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.reference import cpuset as reference  # noqa: E402
from koordinator_tpu import metrics, timeline  # noqa: E402
from koordinator_tpu.api.qos import QoSClass  # noqa: E402
from koordinator_tpu.api.resources import (  # noqa: E402
    NUM_RESOURCE_DIMS,
    ResourceDim,
)
from koordinator_tpu.ops import numa  # noqa: E402
from koordinator_tpu.ops.assignment import (  # noqa: E402
    ScoringConfig,
    greedy_assign,
    score_pods,
)
from koordinator_tpu.ops.batch_assign import batch_assign  # noqa: E402
from koordinator_tpu.scheduler.cpu_manager import CPUManager  # noqa: E402
from koordinator_tpu.scheduler.scheduler import Scheduler  # noqa: E402
from koordinator_tpu.scheduler.snapshot import (  # noqa: E402
    ClusterSnapshot,
    NodeSpec,
    PodSpec,
)
from koordinator_tpu.state.cluster_state import (  # noqa: E402
    ClusterState,
    PodBatch,
)

FULL, SPREAD = reference.BIND_FULL, reference.BIND_SPREAD
NODES, WIDTH = 12, 32
#: aggregate rows that never decide: the cpuset term alone does
BIG_CPU, BIG_MEM = 10_000_000, 10_000_000


@functools.cache
def jitted():
    """(greedy, batch, score), jitted once for the module's cases."""
    import jax

    return (jax.jit(greedy_assign, static_argnames=("with_grants",)),
            jax.jit(batch_assign, static_argnames=("with_grants", "method")),
            jax.jit(score_pods))


def ref_node(cpus: int, single: bool) -> reference.Node:
    """``cpus`` CPUs, 2 threads a core, 2 NUMA nodes; CPU c on core c // 2."""
    core = [c // 2 for c in range(cpus)]
    return reference.Node(core, [c * 2 // cpus for c in range(cpus)],
                          single_numa=single)


def cpu_plane(nodes: list[reference.Node], capacity: int):
    """The reference's nodes as the program's ``CPUState``."""
    import jax.numpy as jnp

    held = np.zeros((capacity, WIDTH), np.int32)
    core = np.zeros((capacity, WIDTH), np.int32)
    sib = np.full((capacity, WIDTH, 2), -1, np.int32)
    numa_id = np.zeros((capacity, WIDTH), np.int32)
    valid = np.zeros((capacity, WIDTH), bool)
    tpc = np.ones(capacity, np.int32)
    single = np.zeros(capacity, bool)
    for row, node in enumerate(nodes):
        k = len(node.core)
        held[row, :k] = node.ref + numa.EXCL_UNIT * node.excl
        core[row, :k] = node.core
        numa_id[row, :k] = node.numa
        valid[row, :k] = True
        for cpu in range(k):
            mates = np.flatnonzero(node.core == node.core[cpu])
            sib[row, cpu, :len(mates)] = mates
        tpc[row] = node.threads
        single[row] = node.single_numa
    return numa.CPUState(
        held=jnp.asarray(held), core=jnp.asarray(core), sib=jnp.asarray(sib),
        numa=jnp.asarray(numa_id), valid=jnp.asarray(valid),
        max_ref=jnp.ones(capacity, jnp.int32), tpc=jnp.asarray(tpc),
        single_numa=jnp.asarray(single))


def fragmented(seed: int, single: bool | None = None, count: int = NODES):
    """A seeded cluster of ``count`` nodes of 16 or 32 CPUs (half
    SingleNUMANode unless ``single`` says), fragmented by random prior
    grants: (the reference's nodes, the program's state with the same CPU
    plane)."""
    rng = np.random.default_rng(seed)
    nodes = [ref_node(int(rng.choice([16, 32])),
                      bool(i % 2) if single is None else single)
             for i in range(count)]
    for _ in range(int(rng.integers(10, 30)) * count // NODES):
        node = nodes[int(rng.integers(count))]
        bind = int(rng.choice([FULL, SPREAD]))
        excl = bool(rng.random() < 0.5)
        cpus = reference.take(node, int(rng.choice([1, 2, 4])), bind, excl)
        if cpus is not None:
            reference.reserve(node, cpus, excl)
    alloc = np.zeros((count, NUM_RESOURCE_DIMS), np.int32)
    alloc[:, ResourceDim.CPU] = BIG_CPU
    alloc[:, ResourceDim.MEMORY] = BIG_MEM
    state = ClusterState.from_arrays(alloc)
    return nodes, state.replace(cpus=cpu_plane(nodes, state.capacity))


def pinned_batch(asks: list[tuple[int, int, bool]], capacity: int = 32,
                 priority: list[int] | None = None,
                 gang_id: list[int] | None = None) -> PodBatch:
    """LSE pods of (cpus, bind, exclusive) each."""
    p = len(asks)
    request = np.zeros((p, NUM_RESOURCE_DIMS), np.int32)
    request[:, ResourceDim.CPU] = [n * 1_000 for n, _, _ in asks]
    request[:, ResourceDim.MEMORY] = 1_024
    spec = [numa.pack_cpu_spec(bind, numa.EXCLUSIVE_PCPU_LEVEL if excl
                               else numa.EXCLUSIVE_NONE)
            for _, bind, excl in asks]
    return PodBatch.build(
        request, qos=np.full(p, int(QoSClass.LSE)), class_capacity=8,
        capacity=capacity, cpu_spec=np.asarray(spec),
        priority=None if priority is None else np.asarray(priority),
        gang_id=None if gang_id is None else np.asarray(gang_id))


def granted(grants, i: int) -> list[int]:
    return np.flatnonzero(np.asarray(grants.cpus.selection[i])).tolist()


@pytest.mark.parametrize("excl", [False, True])
@pytest.mark.parametrize("bind", [FULL, SPREAD])
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_one_pod_filter_equals_the_reference(seed, bind, excl):
    nodes, state = fragmented(seed)
    *_, score = jitted()
    for n in (1, 3, 8, 14):
        _, feasible = score(state, pinned_batch([(n, bind, excl)]),
                            ScoringConfig.default())
        got = np.asarray(feasible)[0, :NODES].tolist()
        want = [reference.fits(node, n, bind, excl) for node in nodes]
        assert got == want, (n, got, want)


@pytest.mark.parametrize("solver", ["greedy", "batch"])
@pytest.mark.parametrize("single", [True, False])
@pytest.mark.parametrize("bind", [FULL, SPREAD])
@pytest.mark.parametrize("seed", [4, 5, 9])
def test_a_batch_matches_the_replay_cpu_for_cpu(seed, bind, single, solver):
    """Every grant of one solve is what the reference's take gives, its
    node's grants replayed in the order the solve took them (their
    turns); nothing is granted twice; the plane that comes back is the
    replay's."""
    nodes, state = fragmented(seed, single)
    rng = np.random.default_rng(seed + 100)
    asks = [(int(rng.choice([1, 2, 4, 6, 8])), bind,
             bool(rng.random() < 0.5)) for _ in range(24)]
    pods = pinned_batch(asks, priority=rng.integers(0, 100, len(asks)))
    greedy, batch, _ = jitted()
    solve = greedy if solver == "greedy" else batch
    a, new_state, _, grants, *_ = solve(state, pods, ScoringConfig.default(),
                                        with_grants=True)
    a = np.asarray(a)[:len(asks)]
    turn = np.asarray(grants.cpus.turn)
    by_node: dict[int, list] = {}
    for i in sorted(range(len(asks)), key=lambda i: turn[i]):
        n, b, excl = asks[i]
        cpus = granted(grants, i)
        if a[i] < 0:
            assert not cpus
            continue
        assert cpus, i
        by_node.setdefault(int(a[i]), []).append((f"p{i}", n, b, excl, cpus))
    assert by_node, "the batch placed nothing"
    assert max(map(len, by_node.values())) > 1, "no node took twice"
    for row, round_grants in by_node.items():
        assert reference.replay_round(nodes[row], round_grants) == 0, (
            row, round_grants)
    held = np.asarray(new_state.cpus.held)
    for row, node in enumerate(nodes):
        k = len(node.core)
        assert held[row, :k].tolist() == (
            node.ref + numa.EXCL_UNIT * node.excl).tolist()
    if solver == "greedy":
        # the exact scan leaves no pinned pod a node's cores would take
        for i, (n, b, excl) in enumerate(asks):
            if a[i] < 0:
                assert not any(reference.fits(node, n, b, excl)
                               for node in nodes)


def test_two_pods_race_for_one_nodes_last_core_in_one_round():
    """One free core in the cluster, two 2-CPU FullPCPUs pods in one
    round: both propose it, the one ahead in priority order is granted,
    the other lost its race and is not assigned."""
    node = ref_node(16, False)
    reference.reserve(node, list(range(14)), True)
    alloc = np.zeros((1, NUM_RESOURCE_DIMS), np.int32)
    alloc[:, ResourceDim.CPU] = BIG_CPU
    alloc[:, ResourceDim.MEMORY] = BIG_MEM
    state = ClusterState.from_arrays(alloc)
    state = state.replace(cpus=cpu_plane([node], state.capacity))
    pods = pinned_batch([(2, FULL, True), (2, FULL, True)],
                        priority=[10, 20])
    _, batch, _ = jitted()
    a, new_state, _, grants = batch(state, pods, ScoringConfig.default(),
                                    with_grants=True)
    a = np.asarray(a)
    assert a[1] == 0 and a[0] == -1
    assert granted(grants, 1) == [14, 15]
    assert granted(grants, 0) == []
    assert int(np.asarray(grants.cpus.lost_races)[0]) == 1
    assert np.asarray(new_state.cpus.held)[0, 14:16].tolist() == [
        1 + numa.EXCL_UNIT] * 2


def test_a_rolled_back_gang_gives_its_cores_back():
    """A gang of three pinned pods of which only two fit is rolled back
    whole: no grant leaves the solve and the plane comes back as it went
    in."""
    from koordinator_tpu.ops.gang import GangInfo, gang_assign

    nodes, state = fragmented(7, single=False)
    free = sum(int(n.eligible(True, True).sum()) for n in nodes)
    ask = free // 2 - free // 2 % 2 - 2
    pods = pinned_batch([(ask, FULL, True)] * 2 + [(WIDTH, FULL, True)],
                        gang_id=[0, 0, 0])
    gangs = GangInfo.build(np.asarray([3], np.int32))
    for solver in ("greedy", "batch"):
        a, new_state, _, grants, _ = gang_assign(
            state, pods, ScoringConfig.default(), gangs, passes=2,
            solver=solver, with_grants=True)
        assert (np.asarray(a)[:3] < 0).all(), solver
        assert not np.asarray(grants.cpus.selection).any(), solver
        assert (np.asarray(new_state.cpus.held)
                == np.asarray(state.cpus.held)).all(), solver


def test_the_scan_entry_filter_prunes_a_pod_no_numa_node_holds():
    """On SingleNUMANode nodes of 2 x 16 CPUs a 20-CPU pod fits no NUMA
    node, though the aggregate rows hold it: the exact scan's entry
    filter drops it, and it never takes a step."""
    nodes = [ref_node(32, True) for _ in range(4)]
    alloc = np.zeros((4, NUM_RESOURCE_DIMS), np.int32)
    alloc[:, ResourceDim.CPU] = BIG_CPU
    alloc[:, ResourceDim.MEMORY] = BIG_MEM
    state = ClusterState.from_arrays(alloc)
    state = state.replace(cpus=cpu_plane(nodes, state.capacity))
    pods = pinned_batch([(20, FULL, True), (4, FULL, True), (2, SPREAD, False)],
                        priority=[100, 1, 1])
    greedy, *_ = jitted()
    a, _, _, grants, stats = greedy(state, pods, ScoringConfig.default(),
                                    with_grants=True)
    assert int(stats.steps) == 2
    assert np.asarray(a)[0] == -1 and (np.asarray(a)[1:3] >= 0).all()
    assert granted(grants, 0) == []


# -- the Reserve loop in blocks ----------------------------------------------


def full_width_cpuset_accept(cpus, held, creq, choice, accept, order_pos):
    """The oracle: the Reserve loop as it was before it granted in blocks.
    Every trip grants on all P rows, each pod on its node's row whether
    or not it is its turn, and keeps the rows whose turn it is.  Returns
    what ``batch_assign._cpuset_accept`` does, the rows as trips x P."""
    import jax
    import jax.numpy as jnp

    p, n = choice.shape[0], held.shape[0]

    def body(c):
        waiting, held, selection, granted, rows = c
        seg = jnp.where(waiting, choice, n)
        first = jax.ops.segment_min(
            jnp.where(waiting, order_pos, p), seg, num_segments=n + 1)
        turn = waiting & (order_pos == first[seg])
        row = jnp.where(turn, choice, 0)
        sel, ok = numa.grant_cpu_rows(
            jax.tree.map(lambda a: a[row], cpus), held[row], creq)
        ok = ok & turn
        sel = sel & ok[:, None]
        held = held.at[row].add(sel * creq.unit[:, None])
        return (waiting & ~turn, held, selection | sel, granted | ok,
                rows + p)

    _, held, selection, granted, rows = jax.lax.while_loop(
        lambda c: jnp.any(c[0]), body,
        (accept & creq.wants, held, jnp.zeros((p, cpus.shape[1]), bool),
         jnp.zeros(p, bool), jnp.int32(0)))
    return held, selection, granted, rows


@functools.cache
def accept_loops():
    """(the blocked loop at width 8, the full-width oracle), jitted."""
    import jax

    from koordinator_tpu.ops import batch_assign as ba

    return (jax.jit(functools.partial(ba._cpuset_accept, width=8)),
            jax.jit(full_width_cpuset_accept))


#: plane -> (SingleNUMANode nodes, bind policies, exclusive share)
ACCEPT_PLANES = {
    "single_numa": (True, (FULL, SPREAD), 0.5),
    "spread": (False, (SPREAD,), 0.0),
    "exclusive": (None, (FULL,), 1.0),
}


@pytest.mark.parametrize("plane", sorted(ACCEPT_PLANES))
@pytest.mark.parametrize("seed", [21, 22])
def test_the_blocked_reserve_loop_grants_what_the_full_width_one_did(
        seed, plane, monkeypatch):
    """64 accepted pinned proposals on 40 fragmented nodes, up to a dozen
    on one node, granted 8 pods a trip, leave the plane, the selections
    and the granted set the loop over all 64 rows a trip left; and a
    whole solve in blocks of 8 gives that loop's solve's assignments,
    grants, lost races and turns."""
    import jax
    import jax.numpy as jnp

    from koordinator_tpu.ops import batch_assign as ba

    single, binds, excl_share = ACCEPT_PLANES[plane]
    nodes, state = fragmented(seed, single, count=40)
    rng = np.random.default_rng(seed + 200)
    asks = [(int(rng.choice([1, 2, 4, 6, 8])), int(rng.choice(binds)),
             bool(rng.random() < excl_share)) for _ in range(64)]
    pods = pinned_batch(asks, capacity=64,
                        priority=rng.integers(0, 100, len(asks)))
    creq = numa.pod_cpu_requests(pods)
    # half the pods crowd onto 5 nodes, the rest spread over all 40: a
    # first trip of more than 8 turns, then several trips on the crowd
    choice = jnp.asarray(np.where(rng.random(64) < 0.5,
                                  rng.integers(0, 5, 64),
                                  rng.integers(0, 40, 64)), jnp.int32)
    accept = jnp.asarray(rng.random(64) < 0.9)
    order_pos = jnp.asarray(rng.permutation(64), jnp.int32)
    blocked, oracle = accept_loops()
    args = (state.cpus, state.cpus.held, creq, choice, accept, order_pos)
    got, want = blocked(*args), oracle(*args)
    for name, g, w in zip(("held", "selection", "granted"), got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w), name)
    assert np.asarray(want[2]).any() and int(got[3]) % 8 == 0
    assert int(got[3]) < int(want[3])

    def solve():
        # a fresh function each call: jit reuses a trace of the same one,
        # and the patches below must be traced
        return jax.jit(lambda *a: ba.batch_assign(*a, with_grants=True))(
            state, pods, ScoringConfig.default())

    monkeypatch.setattr(ba, "ACCEPT_BLOCK", 8)
    a, new_state, _, grants = solve()
    monkeypatch.setattr(
        ba, "_cpuset_accept",
        lambda *args: full_width_cpuset_accept(*args[:6]))
    a0, state0, _, grants0 = solve()
    assert int(grants.accept_rows) < int(grants0.accept_rows)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(a0))
    assert (np.asarray(a) >= 0).sum() > 8
    np.testing.assert_array_equal(np.asarray(new_state.cpus.held),
                                  np.asarray(state0.cpus.held))
    for name in ("selection", "lost_races", "turn"):
        np.testing.assert_array_equal(
            np.asarray(getattr(grants.cpus, name)),
            np.asarray(getattr(grants0.cpus, name)), name)


def test_the_loop_counts_the_rows_of_its_blocks():
    """Node 0 takes five pinned pods, 30 other nodes one each, in blocks
    of 8: node 0's pods lead the priority order at indices 0-4, so each
    trip serves node 0's next pod and 7 others, five trips of 8 rows."""
    import jax.numpy as jnp

    _, state = fragmented(31, single=False, count=40)
    state = state.replace(cpus=state.cpus.replace(
        held=jnp.zeros_like(state.cpus.held)))
    pods = pinned_batch([(2, FULL, False)] * 35, capacity=64)
    creq = numa.pod_cpu_requests(pods)
    choice = jnp.asarray([0] * 5 + list(range(1, 31)) + [0] * 29, jnp.int32)
    accept = jnp.arange(64) < 35
    blocked, oracle = accept_loops()
    args = (state.cpus, state.cpus.held, creq, choice, accept,
            jnp.arange(64, dtype=jnp.int32))
    _, _, granted_, rows = blocked(*args)
    assert np.asarray(granted_)[:35].all()
    assert int(rows) == 40
    # the full-width loop's five trips each granted on all 64 rows
    assert int(oracle(*args)[3]) == 5 * 64


# -- the kernels against their gather forms ----------------------------------


@pytest.mark.parametrize("solver", ["batch", "greedy"])
@pytest.mark.parametrize("seed", [41, 42])
def test_a_solve_grants_what_the_gather_forms_granted(seed, solver,
                                                      monkeypatch):
    """48 pinned pods on 12 fragmented nodes: the propose/accept rounds
    (their Reserve loop in blocks of 8) or the exact scan (its per-step
    take and one-column pools) give the same assignments, selections,
    lost races, turns, rows granted on, scan steps and plane with the
    compare-and-reduce kernels as with their gather forms
    (``tests/numa_reference.py``) in their place."""
    import jax

    from koordinator_tpu.ops import batch_assign as ba
    from tests import numa_reference

    _, state = fragmented(seed)
    rng = np.random.default_rng(seed + 300)
    asks = [(int(rng.choice([1, 2, 4, 6, 8])), int(rng.choice([FULL, SPREAD])),
             bool(rng.random() < 0.5)) for _ in range(48)]
    pods = pinned_batch(asks, capacity=64,
                        priority=rng.integers(0, 100, len(asks)))
    fn = ba.batch_assign if solver == "batch" else greedy_assign
    monkeypatch.setattr(ba, "ACCEPT_BLOCK", 8)

    def solve():
        # a fresh function each call: the patch below must be traced
        a, new_state, _, grants, *stats = jax.jit(
            lambda *args: fn(*args, with_grants=True))(
                state, pods, ScoringConfig.default())
        return {"assignments": a, "held": new_state.cpus.held,
                "selection": grants.cpus.selection,
                "lost_races": grants.cpus.lost_races,
                "turn": grants.cpus.turn, "accept_rows": grants.accept_rows,
                "steps": stats[0].steps if stats else 0}

    got = solve()
    traced: list[str] = []
    numa_reference.patch(monkeypatch, traced)
    want = solve()
    assert {"_eligible", "grant_cpu_rows"} <= set(traced)
    for name in got:
        np.testing.assert_array_equal(np.asarray(got[name]),
                                      np.asarray(want[name]), name)
    placed = int((np.asarray(got["assignments"])[:48] >= 0).sum())
    assert 8 < placed < 48, placed
    assert np.asarray(got["selection"]).any()
    assert solver == "greedy" or np.asarray(got["lost_races"]).any()


# -- through the scheduler ----------------------------------------------------


def node_spec(name: str, cpus: int = 16) -> NodeSpec:
    alloc = np.zeros(NUM_RESOURCE_DIMS, np.int32)
    alloc[ResourceDim.CPU] = cpus * 1_000
    alloc[ResourceDim.MEMORY] = cpus * 4_096
    return NodeSpec(name=name, allocatable=alloc)


def pinned(name: str, cpus: int, bind: int = FULL,
           qos: QoSClass = QoSClass.LSR, priority: int = 0) -> PodSpec:
    req = np.zeros(NUM_RESOURCE_DIMS, np.int32)
    req[ResourceDim.CPU] = cpus * 1_000
    req[ResourceDim.MEMORY] = 1_024
    return PodSpec(name=name, requests=req, qos=int(qos),
                   cpu_bind_policy=bind, priority=priority)


def mk_scheduler(names: list[str], cpus: int = 16, **kwargs):
    cm = CPUManager()
    snap = ClusterSnapshot(capacity=16)
    for name in names:
        snap.upsert_node(node_spec(name, cpus))
        cm.register_node(name, numa.CPUTopology.uniform(
            sockets=1, numa_per_socket=2, cores_per_numa=cpus // 4))
    import jax.numpy as jnp

    # no LoadAware threshold: the rows and the cores decide
    cfg = ScoringConfig.default().replace(
        usage_thresholds=jnp.zeros(NUM_RESOURCE_DIMS, jnp.int32),
        estimator_defaults=jnp.zeros(NUM_RESOURCE_DIMS, jnp.int32))
    return Scheduler(snap, config=cfg, cpu_manager=cm, **kwargs), cm


def cpuset_of(sched, pod: str) -> list[int]:
    status = sched.resource_status[pod]["resource-status"]
    return sorted(int(c) for c in status["cpuset"].split(","))


def test_a_round_with_the_stage_takes_no_cpuset_on_the_host(monkeypatch):
    """The grants are the solve's: no ``CPUManager.allocate`` (the host
    take) at the commit; the books and the annotation hold them."""
    sched, cm = mk_scheduler(["n0", "n1"])

    def refuse(*args, **kwargs):
        raise AssertionError("a host take in a round with the stage")

    monkeypatch.setattr(CPUManager, "allocate", refuse)
    for i in range(4):
        sched.enqueue(pinned(f"p{i}", 4, FULL if i % 2 else SPREAD))
    res = sched.schedule_round()
    assert len(res.assignments) == 4
    for i in range(4):
        node = res.assignments[f"p{i}"]
        assert cpuset_of(sched, f"p{i}") == cm.node(node).allocations[
            f"p{i}"].cpus
        assert len(cpuset_of(sched, f"p{i}")) == 4


def test_the_books_record_a_rounds_grants_in_the_order_they_were_taken():
    """The commit writes the solve's grants into the books in their
    turns' order, whatever the bind order: a node's allocations are the
    order the deployment's replay takes them in."""
    from koordinator_tpu.scheduler.scheduler import SchedulingResult

    sched, cm = mk_scheduler(["n0"])
    binds = [(pinned(f"p{i}", 2), "n0") for i in range(3)]
    cpus = np.zeros((3, 16), bool)
    for i in range(3):
        cpus[i, 2 * i:2 * i + 2] = True
    with sched.lock:
        refused = sched._grant_cpusets(
            binds, {"cpus": cpus, "cpus.turn": np.array([7, 0, 3])},
            SchedulingResult({}, {}))
    assert not refused
    assert list(cm.node("n0").allocations) == ["p1", "p2", "p0"]
    assert cpuset_of(sched, "p2") == [4, 5]


def test_an_ungranted_pinned_pod_stays_pending_with_a_cpuset_diagnosis():
    """16 CPUs, 8 cores: 7 and 5 FullPCPUs CPUs round up to 4 + 3 cores;
    a 3-CPU pod fits the aggregate row (15 of 16) and no core pair."""
    sched, _ = mk_scheduler(["n0"])
    sched.enqueue(pinned("big", 7, priority=9))
    sched.enqueue(pinned("mid", 5, priority=8))
    sched.enqueue(pinned("second", 3, priority=1))
    res = sched.schedule_round()
    assert {"big", "mid"} <= set(res.assignments)
    assert "second" in res.failures and "second" in sched.pending
    assert "cpuset" in res.failures["second"].message()


def test_release_then_regrant_takes_the_freed_cores():
    """A pod leaves; its cores fold back into the plane at the next read
    and the next pod of the same ask is granted exactly them."""
    sched, cm = mk_scheduler(["n0"])
    sched.enqueue(pinned("a", 8))
    sched.enqueue(pinned("b", 8))
    sched.schedule_round()
    freed = cpuset_of(sched, "a")
    sched.delete_pod("a")
    assert "a" not in cm.node("n0").allocations
    sched.enqueue(pinned("c", 8))
    res = sched.schedule_round()
    assert res.assignments == {"c": "n0"}
    assert cpuset_of(sched, "c") == freed
    with sched.lock:
        held = np.asarray(sched.snapshot.state.cpus.held)[
            sched.snapshot.node_index["n0"], :16]
    assert held.tolist() == cm.node("n0").held().tolist()


def test_span_and_counter_read_the_grants():
    """``bind.cpuset`` (n = grants recorded, under ``phase.Bind``),
    ``release.cpuset`` (n = pods, under ``release.fine_grained``) and
    ``cpuset_grants_total{outcome}``."""
    import time

    from benchmarks import program_spans

    sched, _ = mk_scheduler(["n0"])
    before = {k["outcome"]: v for k, v in metrics.cpuset_grants.items()}
    t_open = time.perf_counter()
    sched.enqueue(pinned("a", 8))
    sched.enqueue(pinned("b", 8))
    sched.enqueue(pinned("c", 8, qos=QoSClass.LSE))
    sched.schedule_round()
    sched.delete_pod("a")
    sched.schedule_round()   # closes the ingest window the release is in

    class Ctx:
        timeline_docs = timeline.RECORDER.cycles(64)
        t_close = time.perf_counter()

    Ctx.t_open = t_open
    recs = program_spans.records(Ctx)
    grants = [r for r in recs if r["name"] == "bind.cpuset"]
    assert grants and all(r["parent"] == "phase.Bind" for r in grants)
    assert program_spans.total(
        recs, lambda r: r["name"] == "bind.cpuset")[1] >= 2
    releases = [r for r in recs if r["name"] == "release.cpuset"]
    assert releases and all(r["parent"] == "release.fine_grained"
                            for r in releases)
    after = {k["outcome"]: v for k, v in metrics.cpuset_grants.items()}
    moved = {k: v - before.get(k, 0) for k, v in after.items()}
    # a and b, then c once a has left; c waited a round first
    assert moved.get("granted") == 3 and moved.get("no_cpuset", 0) >= 1


def test_a_batch_round_records_the_rows_its_reserve_loop_granted_on():
    """The flight record's ``accept_rows``: a batch round over the CPU
    plane reads its solves' trips x block (the batch of 8 rows is one
    block, at least one trip); nothing else in the round adds to it."""
    sched, _ = mk_scheduler(["n0", "n1"], batch_solver_threshold=2)
    for i in range(4):
        sched.enqueue(pinned(f"p{i}", 4, FULL if i % 2 else SPREAD))
    res = sched.schedule_round()
    assert sched.last_solver == "batch" and len(res.assignments) == 4
    rows = sched.flight_recorder.last().accept_rows
    assert rows > 0 and rows % 8 == 0
