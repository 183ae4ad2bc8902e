"""koord-descheduler beside koord-scheduler (both assembled by ``MAINS``):
LowNodeLoad over the scheduler's own state and bound-pod columns, victims
and arbitration equal to the plain reference, reservation-first as ONE
batched round, and the columns in step with ``Scheduler.bound``.
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.reference import lownodeload as reference  # noqa: E402
from koordinator_tpu import metrics, timeline  # noqa: E402
from koordinator_tpu.api import extension as ext  # noqa: E402
from koordinator_tpu.api.resources import (  # noqa: E402
    NUM_RESOURCE_DIMS,
    ResourceDim,
    resource_vector,
)
from koordinator_tpu.cmd.binaries import MAINS  # noqa: E402
from koordinator_tpu.descheduler import lownodeload as lnl  # noqa: E402
from koordinator_tpu.descheduler import plugins as dplugins  # noqa: E402
from koordinator_tpu.descheduler.framework import PDB, EvictorFilter  # noqa: E402
from koordinator_tpu.descheduler.migration import (  # noqa: E402
    ArbitrationLimits,
    ControllerFinder,
    MigrationController,
    MigrationJob,
    MigrationJobPhase,
    Workload,
)
from koordinator_tpu.scheduler.reservations import ReservationPhase  # noqa: E402
from koordinator_tpu.scheduler.scheduler import (  # noqa: E402
    BoundPod,
    PdbRecord,
    Scheduler,
)
from koordinator_tpu.scheduler.snapshot import (  # noqa: E402
    ClusterSnapshot,
    NodeSpec,
    PodSpec,
)

R = NUM_RESOURCE_DIMS
CPU, MEM = int(ResourceDim.CPU), int(ResourceDim.MEMORY)


# -- the assembled pair, driven by the benchmark's own deployment -------------

DEVIATION_YAML = """
apiVersion: descheduler/v1alpha2
kind: DeschedulerConfiguration
profiles:
- name: koord-descheduler
  pluginConfig:
  - name: LowNodeLoad
    args:
      lowThresholds: {cpu: 1, memory: 1}
      highThresholds: {cpu: 20, memory: 40}
      useDeviationThresholds: true
"""


def small_config(tmp_path, deviation: bool) -> tuple[dict, dict]:
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "lownodeload-10k.json")) as f:
        config = json.load(f)
    sizes = {"nodes": 128, "fill_pods": 1500, "workloads": 30,
             "daemonset_workloads": 3,
             "standing": 0, "namespaces": 8}
    if deviation:
        path = tmp_path / "descheduler.yaml"
        path.write_text(DEVIATION_YAML)
        config["descheduler_flags"] = ["--config", str(path)]
        config["program_defaults"].update(
            low_thresholds={"cpu": 1, "memory": 1},
            high_thresholds={"cpu": 20, "memory": 40},
            use_deviation_thresholds=True)
    return config, sizes


@pytest.mark.parametrize("deviation", [False, True],
                         ids=["thresholds", "deviation"])
@pytest.mark.parametrize("seed", [3, 2_147_483_999])
def test_assembled_pair_equals_reference_and_keeps_every_guarantee(
        tmp_path, monkeypatch, seed, deviation):
    """Both ``MAINS`` on 128 nodes, several rounds with the anomaly gate:
    every number the configuration's guarantees compare reads 0, victims
    and arbitration equal the reference's, one reservation round a
    reconcile."""
    from benchmarks.deployments import colocated_descheduler
    from benchmarks.kinds import rebalance_closed
    from benchmarks.spans import Spans

    monkeypatch.chdir(tmp_path)
    config, sizes = small_config(tmp_path, deviation)
    params = {"fill_waves": 3, "hot_nodes_start": 16, "heat_per_cycle": 4,
              "settle": 2, "paths": {}}
    dep = colocated_descheduler.Deployment(config, sizes, seed, "run")
    try:
        spans = Spans(False)
        rebalance_closed.setup(dep, params, spans)
        dep.books.window_open = True
        rounds_before = metrics.migration_reserve_rounds.value()
        cycles = [rebalance_closed.cycle(dep, params, spans, 4)
                  for _ in range(3)]
        compared = dep.verify()
    finally:
        dep.close()
    assert compared == {name: 0 for name in compared}
    # the gate: the set-up's two plain rounds chose nobody, the third did
    assert [len(e["victims"]) for e in dep.desched_log[:2]] == [0, 0]
    assert len(dep.desched_log[2]["victims"]) > 0
    assert sum(c["migrated"] for c in cycles) > 0
    assert dep.books.bound_in_window == len(dep.books.offered) > 0
    reconciles = sum(1 for e in dep.reconcile_log[-3:] if e["allowed"])
    assert metrics.migration_reserve_rounds.value() - rounds_before \
        == reconciles


def test_descheduler_without_a_scheduler_still_refuses_lownodeload():
    with pytest.raises(SystemExit, match="embedding shell must wire it"):
        MAINS["koord-descheduler"](["--disable-leader-election",
                                    "--deschedule-plugins", "LowNodeLoad"])


# -- a scheduler with bound pods of every kind the filters tell apart ---------

def node(name, cpu=16_000, mem=65_536, usage_cpu=0):
    usage = np.zeros(R, np.int32)
    usage[CPU] = usage_cpu
    return NodeSpec(name=name, allocatable=resource_vector(cpu=cpu,
                                                           memory=mem),
                    usage=usage)


def scheduler_with(nodes, **kw) -> Scheduler:
    snap = ClusterSnapshot(capacity=16)
    for n in nodes:
        snap.upsert_node(n)
    return Scheduler(snap, **kw)


def varied_pods(rng, count: int, nodes: list[str]) -> list[BoundPod]:
    pods = []
    for i in range(count):
        kind = i % 8
        pods.append(BoundPod(
            name=f"ns{i % 3}/p{i}", node=nodes[i % len(nodes)],
            requests=resource_vector(cpu=int(rng.integers(100, 900)),
                                     memory=int(rng.integers(64, 512))),
            priority=int(rng.choice([5_500, 7_500, 9_500, 2_000_000_001])),
            labels=({"app": f"w{i % 4}"} if kind != 7 else {}),
            owner=("DaemonSet/agent" if kind == 1 else f"Deployment/w{i % 4}"),
            local_storage=kind == 2,
            annotations=({ext.ANNOTATION_EVICTION_COST: "-2147483648"}
                         if kind == 3 else
                         {ext.ANNOTATION_EVICTION_COST: "10"} if kind == 4
                         else {}),
            qos=2))
    return pods


FILTERS = {
    "defaults": {},
    "pdb": {"pdbs": [PDB({"app": "w1"}, 0), PDB({"app": "w2"}, 3),
                     PDB({"app": "w1"}, 5)]},
    "priority_threshold": {"priority_threshold": 8_000},
    "daemonsets_allowed": {"evict_daemonsets": True},
    "local_storage_allowed": {"evict_local_storage": True},
    "system_critical_allowed": {"evict_system_critical": True},
    "extra_filter": {"extra_filters": [lambda p: p.namespace != "ns1"]},
    "migrating": {"migrating_fn": lambda: {"ns0/p0", "ns2/p5", "gone"}},
    "all_at_once": {"pdbs": [PDB({"app": "w3"}, 0)],
                    "priority_threshold": 9_000, "evict_local_storage": True,
                    "migrating_fn": lambda: {"ns1/p4"}},
}


@pytest.mark.parametrize("case", FILTERS)
def test_columnar_evictable_mask_equals_filter_pod_by_pod(case):
    rng = np.random.default_rng(5)
    sched = scheduler_with([node("n1"), node("n2"), node("n3")])
    for pod in varied_pods(rng, 48, ["n1", "n2", "n3"]):
        sched.add_bound_pod(pod)
    sched.remove_bound_pod("ns1/p7")      # a dead slot in the middle
    evictor_filter = EvictorFilter(**FILTERS[case])
    cols = sched.bound.columns

    def pod_at(slot):
        return dplugins.pod_info_of(cols, slot)

    mask = evictor_filter.mask(cols, pod_at)
    assert mask.shape == (cols.size,)
    live = np.flatnonzero(cols.live[: cols.size])
    assert len(live) == 47 and not mask[~cols.live[: cols.size]].any()
    want = [evictor_filter.filter(pod_at(int(s)))[0] for s in live]
    assert mask[live].tolist() == want
    assert 0 < sum(want) < len(want) or case == "defaults"


# -- the columns in step with the registry -------------------------------------

def assert_columns_equal_registry(sched: Scheduler) -> None:
    cols, snap = sched.bound.columns, sched.snapshot
    assert len(cols) == len(sched.bound) == int(cols.live[: cols.size].sum())
    rows = cols.node_rows(snap)
    usage = cols.pod_usage()
    for name, bp in sched.bound.items():
        slot = cols.slot_of[name]
        assert cols.live[slot] and cols.names[slot] == name
        row = snap.node_index.get(bp.node)
        if row is not None and (snap.node_generation.get(bp.node, 0)
                                != bp.node_generation):
            row = None
        assert rows[slot] == (-1 if row is None else row), name
        assert cols.requests[slot].tolist() == list(bp.requests)
        assert cols.priority[slot] == bp.priority
        assert cols.qos[slot] == bp.qos
        assert cols.quotas.values[cols.quota_id[slot]] == bp.quota
        assert cols.workloads.values[cols.workload_id[slot]] == (bp.owner
                                                                  or "")
        assert cols.labels_of(int(cols.labelset_id[slot])) == bp.labels
        assert bool(cols.flags[slot] & 1) == bp.non_preemptible
        if not cols.usage_set[slot]:
            assert usage[slot].tolist() == list(bp.requests)
    dead = np.flatnonzero(~cols.live[: cols.size])
    assert all(cols.names[s] is None for s in dead)
    assert (rows[dead] == -1).all()


def bind_some(sched):
    for i in range(12):
        sched.enqueue(PodSpec(
            name=f"web/p{i}", requests=resource_vector(cpu=2_000, memory=1_024),
            priority=7_000 + i, qos=2, labels={"app": "web"},
            owner="Deployment/web"))
    assert len(sched.schedule_round().assignments) == 12


def step_binds(sched):
    bind_some(sched)


def step_releases(sched):
    bind_some(sched)
    for name in ("web/p3", "web/p7"):
        sched.delete_pod(name)
    sched.enqueue(PodSpec(name="web/p12", requests=resource_vector(cpu=500),
                          labels={"app": "web"}))
    sched.schedule_round()     # takes a freed slot
    assert "web/p12" in sched.bound


def step_node_readd(sched):
    bind_some(sched)
    sched.snapshot.remove_node("n2")
    sched.snapshot.upsert_node(node("n2"))
    assert any(bp.node == "n2" for bp in sched.bound.values())
    assert (sched.bound.columns.node_rows(sched.snapshot) == -1).any()


def step_usage(sched):
    bind_some(sched)
    sched.set_pod_usage(["web/p1", "nobody", "web/p2"],
                        np.full((3, R), 7, np.int32))
    cols = sched.bound.columns
    assert cols.pod_usage()[cols.slot_of["web/p1"]].tolist() == [7] * R
    assert cols.pod_usage()[cols.slot_of["web/p2"]].tolist() == [7] * R


def step_preemption(sched):
    for i in range(8):
        sched.add_bound_pod(BoundPod(
            name=f"low/p{i}", node=f"n{i % 2 + 1}",
            requests=resource_vector(cpu=7_000, memory=1_024),
            priority=5_000, labels={"app": "low"}))
    sched.register_pdb(PdbRecord(name="low", selector={"app": "low"},
                                 allowed=8))
    sched.enqueue(PodSpec(name="high/p0", priority=9_900,
                          requests=resource_vector(cpu=9_000, memory=1_024)))
    result = sched.schedule_round()
    assert result.nominations, "the preemptor was nominated"
    assert len(sched.bound) < 8


STEPS = {"binds": step_binds, "releases": step_releases,
         "node_readd": step_node_readd, "usage": step_usage,
         "preemption": step_preemption}


@pytest.mark.parametrize("step", STEPS)
def test_bound_columns_equal_registry_after(step):
    evicted = []
    sched = scheduler_with(
        [node("n1"), node("n2")],
        preempt_fn=lambda victim, by: evicted.append(victim))
    STEPS[step](sched)
    assert_columns_equal_registry(sched)
    if step == "preemption":
        assert evicted and not set(evicted) & set(sched.bound)


def test_registry_keeps_columns_in_step_through_every_mutator():
    sched = scheduler_with([node("n1")])
    reg = sched.bound

    def pod(name):
        return BoundPod(name=name, node="n1", requests=resource_vector(cpu=1))

    reg["a"] = pod("a")
    reg["a"] = pod("a")                  # replace: one slot, not two
    reg.update({"b": pod("b"), "c": pod("c")})
    reg.setdefault("d", pod("d"))
    assert len(reg.columns) == 4
    del reg["b"]
    assert reg.pop("c").name == "c" and reg.pop("zz", None) is None
    with pytest.raises(KeyError):
        reg.pop("zz")
    reg.popitem()
    assert_columns_equal_registry(sched)
    reg.clear()
    assert len(reg.columns) == 0 and not reg.columns.live.any()


# -- selection at the size of the source nodes ---------------------------------

def random_cluster(seed: int, deviation: bool):
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    n, p = 24, 160
    capacity = np.zeros((n, R), np.int32)
    capacity[:, CPU] = rng.integers(8_000, 32_000, n)
    capacity[:, MEM] = rng.integers(16_384, 65_536, n)
    usage = np.zeros((n, R), np.int32)
    usage[:, CPU] = (capacity[:, CPU] * rng.uniform(0.1, 0.98, n)).astype(int)
    usage[:, MEM] = (capacity[:, MEM] * rng.uniform(0.1, 0.98, n)).astype(int)
    valid = rng.random(n) < 0.9
    pod_node = rng.integers(-1, n, p).astype(np.int32)
    pod_usage = np.zeros((p, R), np.int32)
    pod_usage[:, CPU] = rng.integers(50, 3_000, p)
    pod_usage[:, MEM] = rng.integers(64, 6_000, p)
    priority = rng.choice([5_000, 5_000, 7_500, 9_000], p).astype(np.int32)
    evictable = rng.random(p) < 0.8
    counters = rng.integers(0, 5, n).astype(np.int32)
    args = lnl.LowNodeLoadArgs.default()
    if deviation:
        args = args.replace(
            use_deviation=jnp.asarray(True),
            low_thresholds=args.low_thresholds.at[CPU].set(10).at[MEM].set(10),
            high_thresholds=args.high_thresholds.at[CPU].set(15)
            .at[MEM].set(20))
    return (usage, capacity, valid, pod_node, pod_usage, priority, evictable,
            counters, args)


@pytest.mark.parametrize("deviation", [False, True],
                         ids=["thresholds", "deviation"])
@pytest.mark.parametrize("seed", range(4))
def test_source_node_selection_equals_select_victims_and_reference(
        seed, deviation):
    import jax.numpy as jnp

    (usage, capacity, valid, pod_node, pod_usage, priority, evictable,
     counters, args) = random_cluster(seed, deviation)
    # the whole-cluster scan, with the counters as this round leaves them
    _, over = lnl.classify_nodes(jnp.asarray(usage), jnp.asarray(capacity),
                                 jnp.asarray(valid), args)
    after = lnl.update_anomaly_counters(jnp.asarray(counters), over)
    whole = np.asarray(lnl.select_victims(
        jnp.asarray(usage), jnp.asarray(capacity), jnp.asarray(valid),
        jnp.asarray(pod_node), jnp.asarray(pod_usage), jnp.asarray(priority),
        jnp.asarray(evictable), after, args))

    selector = lnl.SourceNodeSelector(args)
    selector.counters = jnp.asarray(counters)
    abnormal, handles = selector.observe(
        jnp.asarray(usage), jnp.asarray(capacity), jnp.asarray(valid))
    on_node = pod_node >= 0
    candidates = np.flatnonzero(evictable & on_node
                                & abnormal[np.where(on_node, pod_node, 0)])
    took = candidates[selector.walk(handles, pod_node, pod_usage, priority,
                                    candidates)]
    assert sorted(took) == np.flatnonzero(whole).tolist()
    assert np.asarray(selector.counters).tolist() == np.asarray(
        after).tolist()

    plain = reference.LowNodeLoad(
        np.asarray(args.low_thresholds), np.asarray(args.high_thresholds),
        bool(args.use_deviation), int(args.anomaly_rounds))
    plain.counters = counters.astype(np.int64)
    want, plain_abnormal = plain.round(usage, capacity, valid, pod_node,
                                       pod_usage, priority, evictable)
    assert sorted(want) == sorted(took)
    assert plain_abnormal.tolist() == abnormal.tolist()
    assert len(took) > 0 or not candidates.size


def test_selection_bucket_only_grows():
    import jax.numpy as jnp

    args = lnl.LowNodeLoadArgs.default()
    selector = lnl.SourceNodeSelector(args)
    assert selector.bucket == selector.MIN_BUCKET == 64
    usage, capacity, valid, pod_node, pod_usage, priority, _, _, _ = (
        random_cluster(1, False))
    _, handles = selector.observe(jnp.asarray(usage), jnp.asarray(capacity),
                                  jnp.asarray(valid))
    for count, bucket in ((70, 128), (5, 128), (0, 128), (129, 256)):
        got = selector.walk(handles, pod_node, pod_usage, priority,
                            np.arange(count))
        assert got.shape == (count,) and selector.bucket == bucket


# -- arbitration ---------------------------------------------------------------

@pytest.mark.parametrize("spec", [None, 3, "20%"], ids=str)
@pytest.mark.parametrize("seed", range(3))
def test_arbitration_equals_reference(seed, spec):
    rng = np.random.default_rng(seed)
    finder = ControllerFinder()
    replicas = {}
    for w in range(6):
        replicas[f"Deployment/w{w}"] = int(rng.integers(2, 40))
        if w < 5:      # the sixth is unknown to the finder
            finder.register(Workload(f"Deployment/w{w}",
                                     replicas[f"Deployment/w{w}"]))
    del replicas["Deployment/w5"]
    limits = ArbitrationLimits(max_migrating_per_node=2,
                               max_migrating_per_namespace=4,
                               max_migrating_per_workload=spec,
                               max_unavailable_per_workload=spec)
    ctl = MigrationController(limits=limits, controller_finder=finder)
    for i in range(60):
        w = int(rng.integers(0, 7))
        ctl.submit(MigrationJob(
            name=f"j{i}", pod=f"p{i}", node=f"n{rng.integers(0, 12)}",
            namespace=f"ns{rng.integers(0, 5)}",
            workload=f"Deployment/w{w}" if w < 6 else "",
            priority=int(rng.choice([5_000, 7_000, 9_000])),
            create_time=float(i)))
    for name in ("j1", "j2", "j3"):
        ctl.jobs[name].phase = MigrationJobPhase.RUNNING

    def doc(job):
        return {"name": job.name, "node": job.node,
                "namespace": job.namespace, "workload": job.workload,
                "priority": job.priority, "created": job.create_time}

    want = reference.arbitrate(
        [doc(j) for j in ctl.pending()], [doc(j) for j in ctl.running()],
        {"per_node": 2, "per_namespace": 4, "migrating_per_workload": spec,
         "unavailable_per_workload": spec}, replicas)
    assert [j.name for j in ctl.arbitrate()] == want
    assert 0 < len(want) < 57
    outcomes = {labels["outcome"]: int(v) for labels, v
                in metrics.migration_jobs_arbitrated.items()}
    assert outcomes["allowed"] == len(want)
    assert sum(outcomes.values()) == 57


# -- reservation-first as one batched round ------------------------------------

def pair_with_pods(pods_per_node: int, cool_nodes: int):
    """A scheduler whose pods all sit on ``hot``; ``cool_nodes`` empty
    nodes join afterwards."""
    sched = scheduler_with([node("hot", cpu=64_000, mem=262_144)])
    for i in range(pods_per_node):
        sched.enqueue(PodSpec(
            name=f"web/p{i}", requests=resource_vector(cpu=2_000, memory=2_048),
            priority=7_000, labels={"app": f"w{i % 3}"},
            owner=f"Deployment/w{i % 3}"))
    assert len(sched.schedule_round().assignments) == pods_per_node
    for i in range(cool_nodes):
        sched.snapshot.upsert_node(node(f"cool{i}"))
    return sched


def charged(sched) -> int:
    sched.snapshot.flush()
    return int(np.asarray(sched.snapshot.state.node_requested).sum())


@pytest.mark.parametrize("jobs", [1, 4, 12])
def test_reserve_many_is_one_round_however_many_jobs(jobs):
    sched = pair_with_pods(12, cool_nodes=3)
    before = charged(sched)
    rounds = sched.round_seq
    reserve_many = dplugins.scheduler_reserve_many(sched)
    asked = [MigrationJob(name=f"j{i}", pod=f"web/p{i}", node="hot",
                          workload=f"Deployment/w{i % 3}")
             for i in range(jobs)]
    asked.append(MigrationJob(name="ghost", pod="web/none", node="hot"))
    out = reserve_many(asked)
    assert sched.round_seq - rounds == 1
    assert metrics.migration_reserve_rounds.value() == 1
    assert out["ghost"] is None and set(out) == {j.name for j in asked}
    # every job: an Available reservation off its source node, or None
    # and nothing left behind (the batch solve decides which)
    held = 0
    for job in asked[:-1]:
        if out[job.name] is None:
            assert sched.reservations.get(f"migrate-{job.name}") is None
            continue
        spec = sched.reservations.get(out[job.name])
        assert spec.phase is ReservationPhase.AVAILABLE
        assert spec.node is not None and spec.node != "hot"
        held += int(spec.requests.sum())
    assert held > 0
    assert charged(sched) - before == held
    assert not [p for p in sched.pending if p.startswith("rsv::")]


def test_reserve_many_that_cannot_place_leaves_nothing_charged():
    sched = pair_with_pods(6, cool_nodes=0)      # only the source node
    before = charged(sched)
    out = dplugins.scheduler_reserve_many(sched)(
        [MigrationJob(name=f"j{i}", pod=f"web/p{i}", node="hot")
         for i in range(6)])
    assert out == {f"j{i}": None for i in range(6)}
    assert metrics.migration_reserve_rounds.value() == 1
    assert charged(sched) == before
    assert not sched.reservations.specs()
    assert not [p for p in sched.pending if p.startswith("rsv::")]


def test_reserve_pods_past_the_prepass_cap_wait_and_never_reach_the_batch_engine():
    """The exact pre-pass is the only reserve-pod path: with 12 asked and
    a cap of 4, one round opens 4 reservations; the other 8 jobs come back
    None with nothing charged, and no reserve-pod was left to the general
    solve (which would have placed it: the nodes are empty)."""
    sched = pair_with_pods(12, cool_nodes=6)
    sched.rsv_prepass_cap = 4
    before = charged(sched)
    out = dplugins.scheduler_reserve_many(sched)(
        [MigrationJob(name=f"j{i}", pod=f"web/p{i}", node="hot")
         for i in range(12)])
    opened = [name for name in out.values() if name is not None]
    assert len(opened) == 4
    assert metrics.migration_reserve_rounds.value() == 1
    assert charged(sched) - before == sum(
        int(sched.reservations.get(name).requests.sum()) for name in opened)
    assert len(sched.reservations.specs()) == 4
    assert not [p for p in sched.pending if p.startswith("rsv::")]
    # left alone, the ones past the cap are placed by the NEXT round's
    # pre-pass, four at a time
    from koordinator_tpu.scheduler.reservations import (
        OwnerMatcher,
        ReservationSpec,
    )
    for i in range(6):
        sched.add_reservation(ReservationSpec(
            name=f"later{i}", requests=resource_vector(cpu=500, memory=256),
            owners=[OwnerMatcher(labels={"app": "none"})]))
    available = []
    for _ in range(2):
        sched.schedule_round()
        available.append(sum(
            1 for i in range(6)
            if sched.reservations.get(f"later{i}").phase
            is ReservationPhase.AVAILABLE))
    assert available == [4, 6]


def test_pods_the_prepass_settles_are_no_dirty_rows_of_the_incremental_solve():
    """A replacement round: every new pod binds into its reservation in
    the pre-pass, so the batch solve over the standing queue stays on the
    incremental path however many replacements arrive."""
    from koordinator_tpu.scheduler.reservations import (
        OwnerMatcher,
        ReservationSpec,
    )

    # 128 node rows: no other suite's recompile count hangs on that shape
    snap = ClusterSnapshot(capacity=128)
    for i in range(48):
        snap.upsert_node(node(f"n{i}"))
    sched = Scheduler(snap)
    sched.batch_solver_threshold = 8
    for i in range(16):     # the standing queue: pods that fit no node
        sched.enqueue(PodSpec(name=f"whale{i}", priority=9_500,
                              requests=resource_vector(cpu=900_000,
                                                       memory=64)))
    sched.schedule_round()
    for i in range(12):
        sched.add_reservation(ReservationSpec(
            name=f"r{i}", requests=resource_vector(cpu=1_000, memory=1_024),
            owners=[OwnerMatcher(labels={"app": f"w{i}"})],
            allocate_once=True))
    sched.schedule_round()
    assert all(sched.reservations.get(f"r{i}").phase
               is ReservationPhase.AVAILABLE for i in range(12))
    sched.schedule_round()      # a quiet round: the cache is warm
    for i in range(12):         # 12 new pods beside 16 standing: 43 %
        sched.enqueue(PodSpec(name=f"web/q{i}", priority=7_000,
                              requests=resource_vector(cpu=1_000,
                                                       memory=1_024),
                              labels={"app": f"w{i}"}))
    result = sched.schedule_round()
    assert sched.last_solver == "batch"
    assert sched.last_solve_path == "incremental"
    for i in range(12):
        bound = sched.bound[f"web/q{i}"]
        assert bound.reservation == f"r{i}"
        assert bound.node == sched.reservations.get(f"r{i}").node
    assert set(result.failures) == {f"whale{i}" for i in range(16)}
    # the next round, with nothing new, is incremental too and still
    # holds the standing pods' rows
    sched.schedule_round()
    assert sched.last_solve_path == "incremental"


def test_reconcile_hands_all_jobs_to_one_call_and_records_its_spans():
    calls = []

    def reserve_many(jobs):
        calls.append([j.name for j in jobs])
        return {j.name: (None if j.name == "j2" else f"rsv-{j.name}")
                for j in jobs}

    evicted = []
    ctl = MigrationController(
        limits=ArbitrationLimits(max_migrating_per_node=8),
        reserve_many=reserve_many,
        evict_fn=lambda job: evicted.append(job.pod) or True)
    for i in range(5):
        ctl.submit(MigrationJob(name=f"j{i}", pod=f"p{i}", node="n1",
                                create_time=float(i)))
    assert ctl.migrating_pods() == {f"p{i}" for i in range(5)}
    import time

    t0 = time.perf_counter()
    ctl.reconcile()
    doc = timeline.RECORDER.finish_cycle(1, t0, time.perf_counter(),
                                         publish=False)
    assert calls == [[f"j{i}" for i in range(5)]]
    assert evicted == ["p0", "p1", "p3", "p4"]
    assert ctl.jobs["j2"].reason == "ReservationFailed"
    assert ctl.migrating_pods() == set()
    spans = {s["name"]: s for s in doc["segments"]}
    assert spans["migrate.arbitrate"]["n"] == 5
    assert spans["migrate.reserve"]["n"] == 5
    assert spans["migrate.evict"]["n"] == 4
    for child in ("migrate.arbitrate", "migrate.reserve", "migrate.evict"):
        assert spans[child]["parent"] == "migrate.reconcile"


def test_balance_beside_a_scheduler_records_its_spans_and_counters():
    sched = pair_with_pods(10, cool_nodes=2)
    sched.snapshot.upsert_node(node("hot", cpu=64_000, mem=262_144,
                                    usage_cpu=60_000))
    descheduler = MAINS["koord-descheduler"](
        ["--disable-leader-election", "--deschedule-plugins", "LowNodeLoad"],
        scheduler=type("Asm", (), {"component": sched})())
    assert descheduler.migration is not None
    sched.set_pod_usage([f"web/p{i}" for i in range(10)],
                        np.tile(resource_vector(cpu=5_000, memory=2_048),
                                (10, 1)))
    import time

    t0 = time.perf_counter()
    for _ in range(3):
        descheduler.component.run_once()
    doc = timeline.RECORDER.finish_cycle(1, t0, time.perf_counter(),
                                         publish=False)
    jobs = descheduler.migration.jobs
    assert 0 < len(jobs) < 10
    assert metrics.descheduler_victims_total.value(
        {"plugin": "LowNodeLoad"}) == len(jobs)
    by_name = doc["by_name"]
    assert by_name["desched.round"]["n"] == 3
    spans = [s for s in doc["segments"] if s["name"].startswith("desched.")]
    parents = {s["name"]: s["parent"] for s in spans}
    assert parents == {"desched.round": "", "desched.stage": "desched.round",
                       "desched.select": "desched.round",
                       "desched.submit": "desched.round"}
    assert by_name["desched.submit"]["n"] == len(jobs)
    # a pod with a live job is not chosen again
    descheduler.component.run_once()
    assert len(descheduler.migration.jobs) >= len(jobs)
    pods = [j.pod for j in descheduler.migration.jobs.values()]
    assert len(pods) == len(set(pods))
