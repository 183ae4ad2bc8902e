import jax.numpy as jnp
import numpy as np

from koordinator_tpu.api.resources import NUM_RESOURCE_DIMS, ResourceDim
from koordinator_tpu.descheduler.lownodeload import (
    LowNodeLoadArgs,
    classify_nodes,
    eviction_budget,
    effective_thresholds,
    select_victims,
    update_anomaly_counters,
    usage_percent,
)
from koordinator_tpu.descheduler.migration import (
    ArbitrationLimits,
    MigrationController,
    MigrationJob,
    MigrationJobPhase,
)

R = NUM_RESOURCE_DIMS
CPU, MEM = ResourceDim.CPU, ResourceDim.MEMORY


def mk(n, cpu_cap=10_000, mem_cap=100_000):
    cap = np.zeros((n, R), np.int32)
    cap[:, CPU], cap[:, MEM] = cpu_cap, mem_cap
    return cap


def usage_of(cap, cpu_pct, mem_pct):
    u = np.zeros_like(cap)
    u[:, CPU] = cap[:, CPU] * np.asarray(cpu_pct) // 100
    u[:, MEM] = cap[:, MEM] * np.asarray(mem_pct) // 100
    return u


def test_classify_under_over():
    cap = mk(4)
    usage = usage_of(cap, [20, 50, 80, 30], [30, 50, 50, 90])
    valid = np.ones(4, bool)
    under, over = classify_nodes(
        jnp.asarray(usage), jnp.asarray(cap), jnp.asarray(valid),
        LowNodeLoadArgs.default(),  # low 45/60, high 65/80
    )
    # node0: all below low -> under; node1: between -> neither;
    # node2: cpu 80 > 65 -> over; node3: mem 90 > 80 -> over
    assert np.asarray(under).tolist()[:4] == [True, False, False, False]
    assert np.asarray(over).tolist()[:4] == [False, False, True, True]


def test_deviation_thresholds():
    cap = mk(2)
    usage = usage_of(cap, [30, 70], [50, 50])
    args = LowNodeLoadArgs.default().replace(
        low_thresholds=jnp.full(R, -1, jnp.int32).at[CPU].set(10),
        high_thresholds=jnp.full(R, -1, jnp.int32).at[CPU].set(10),
        use_deviation=jnp.asarray(True),
    )
    pct = usage_percent(jnp.asarray(usage), jnp.asarray(cap))
    low, high = effective_thresholds(args, pct, jnp.asarray(np.ones(2, bool)))
    # mean cpu = 50 -> low 40, high 60
    assert int(low[CPU]) == 40
    assert int(high[CPU]) == 60
    under, over = classify_nodes(
        jnp.asarray(usage), jnp.asarray(cap), jnp.asarray(np.ones(2, bool)), args
    )
    assert np.asarray(under).tolist() == [True, False]
    assert np.asarray(over).tolist() == [False, True]


def test_anomaly_counter():
    c = jnp.asarray(np.zeros(3, np.int32))
    over = jnp.asarray(np.array([True, True, False]))
    c = update_anomaly_counters(c, over)
    c = update_anomaly_counters(c, jnp.asarray(np.array([True, False, False])))
    assert np.asarray(c).tolist() == [2, 0, 0]


def test_eviction_budget():
    cap = mk(2)
    usage = usage_of(cap, [20, 90], [30, 90])
    args = LowNodeLoadArgs.default()
    pct = usage_percent(jnp.asarray(usage), jnp.asarray(cap))
    _, high = effective_thresholds(args, pct, jnp.asarray(np.ones(2, bool)))
    under = jnp.asarray(np.array([True, False]))
    b = eviction_budget(jnp.asarray(usage), jnp.asarray(cap), under, high)
    # node0: cpu 65%*10000 - 2000 = 4500; mem 80%*100000 - 30000 = 50000
    assert int(b[CPU]) == 4_500
    assert int(b[MEM]) == 50_000


def select(usage, cap, pod_node, pod_usage, prio, evictable=None, counters=None,
           args=None):
    n = cap.shape[0]
    p = len(pod_node)
    return np.asarray(select_victims(
        jnp.asarray(usage), jnp.asarray(cap), jnp.asarray(np.ones(n, bool)),
        jnp.asarray(np.asarray(pod_node, np.int32)),
        jnp.asarray(pod_usage),
        jnp.asarray(np.asarray(prio, np.int32)),
        jnp.asarray(np.ones(p, bool) if evictable is None else evictable),
        jnp.asarray(np.full(n, 99, np.int32) if counters is None else counters),
        args or LowNodeLoadArgs.default(),
    ))


def test_select_victims_rebalances_hot_node():
    cap = mk(2)
    usage = usage_of(cap, [90, 20], [50, 20])  # node0 hot on cpu, node1 cold
    pod_usage = np.zeros((3, R), np.int32)
    pod_usage[:, CPU] = [3_000, 2_000, 1_000]
    victims = select(usage, cap, [0, 0, 0], pod_usage, [9_000, 5_000, 3_000])
    # evict cheapest first: pod2 (1000, prio 3000) -> node at 80% still > 65;
    # pod1 (2000) -> 60% <= 65 stop. pod0 survives.
    assert victims.tolist()[:3] == [False, True, True]


def test_select_victims_respects_budget():
    cap = mk(2)
    usage = usage_of(cap, [90, 60], [50, 20])  # node1 not under (cpu 60 >= 45)
    pod_usage = np.zeros((1, R), np.int32)
    pod_usage[0, CPU] = 1_000
    victims = select(usage, cap, [0], pod_usage, [3_000])
    # no underutilized nodes -> zero budget -> nothing evicted
    assert not victims.any()


def test_select_victims_needs_anomaly_rounds():
    cap = mk(2)
    usage = usage_of(cap, [90, 20], [50, 20])
    pod_usage = np.zeros((1, R), np.int32)
    pod_usage[0, CPU] = 1_000
    victims = select(usage, cap, [0], pod_usage, [3_000],
                     counters=np.array([1, 0], np.int32))  # < 3 rounds
    assert not victims.any()


def test_select_victims_skips_unevictable():
    cap = mk(2)
    usage = usage_of(cap, [90, 20], [50, 20])
    pod_usage = np.zeros((2, R), np.int32)
    pod_usage[:, CPU] = [2_000, 2_000]
    victims = select(usage, cap, [0, 0], pod_usage, [3_000, 3_000],
                     evictable=np.array([False, True]))
    assert victims.tolist()[:2] == [False, True]


# -- migration controller ----------------------------------------------------


def test_migration_lifecycle_with_reservation():
    evicted = []
    ctl = MigrationController(
        reserve_many=lambda jobs: {j.name: f"resv-{j.pod}" for j in jobs},
        evict_fn=lambda j: evicted.append(j.pod) or True,
    )
    ctl.submit(MigrationJob(name="j1", pod="p1", node="n1"))
    ctl.reconcile()
    job = ctl.jobs["j1"]
    assert job.phase is MigrationJobPhase.SUCCEEDED
    assert job.reservation == "resv-p1"
    assert evicted == ["p1"]


def test_migration_reservation_failure():
    ctl = MigrationController(
        reserve_many=lambda jobs: {j.name: None for j in jobs})
    ctl.submit(MigrationJob(name="j1", pod="p1", node="n1"))
    ctl.reconcile()
    assert ctl.jobs["j1"].phase is MigrationJobPhase.FAILED
    assert ctl.jobs["j1"].reason == "ReservationFailed"


def test_migration_group_limits_per_node():
    ctl = MigrationController(
        limits=ArbitrationLimits(max_migrating_per_node=1),
        evict_fn=lambda j: False,  # stays running
    )
    ctl.submit(MigrationJob(name="j1", pod="p1", node="n1", create_time=1))
    ctl.submit(MigrationJob(name="j2", pod="p2", node="n1", create_time=2))
    ctl.submit(MigrationJob(name="j3", pod="p3", node="n2", create_time=3))
    ctl.reconcile()
    phases = {n: j.phase for n, j in ctl.jobs.items()}
    assert phases["j1"] is MigrationJobPhase.RUNNING
    assert phases["j2"] is MigrationJobPhase.PENDING  # node n1 at limit
    assert phases["j3"] is MigrationJobPhase.RUNNING


def test_migration_workload_unavailable_budget():
    ctl = MigrationController(
        limits=ArbitrationLimits(max_unavailable_per_workload=1),
        workload_unavailable_fn=lambda w: 1,  # already one unavailable
        evict_fn=lambda j: True,
    )
    ctl.submit(MigrationJob(name="j1", pod="p1", node="n1", workload="w1"))
    ctl.reconcile()
    assert ctl.jobs["j1"].phase is MigrationJobPhase.PENDING


def test_migration_sort_lower_priority_first():
    started = []
    ctl = MigrationController(
        limits=ArbitrationLimits(max_migrating_per_node=1),
        evict_fn=lambda j: started.append(j.pod) or True,
    )
    ctl.submit(MigrationJob(name="j1", pod="hi", node="n1", priority=9_500,
                            create_time=1))
    ctl.submit(MigrationJob(name="j2", pod="lo", node="n1", priority=3_000,
                            create_time=2))
    ctl.reconcile()
    # only one runs (node limit); the lower-priority pod goes first
    assert started == ["lo"]


def test_migration_timeout():
    t = [0.0]
    ctl = MigrationController(evict_fn=lambda j: False, clock=lambda: t[0])
    ctl.submit(MigrationJob(name="j1", pod="p1", node="n1", timeout_sec=10))
    ctl.reconcile()
    assert ctl.jobs["j1"].phase is MigrationJobPhase.RUNNING
    t[0] = 100.0
    ctl.reconcile()
    assert ctl.jobs["j1"].phase is MigrationJobPhase.FAILED
    assert ctl.jobs["j1"].reason == "Timeout"


# ---- controllerfinder: workload-derived budgets (migration/util/util.go:81,
# arbitrator/filter.go:409) --------------------------------------------------

def test_get_max_unavailable_defaults_and_scaling():
    from koordinator_tpu.descheduler.migration import get_max_unavailable

    # replica-count-dependent defaults when unspecified
    assert get_max_unavailable(1, None) == 1
    assert get_max_unavailable(3, None) == 1
    assert get_max_unavailable(4, None) == 2
    assert get_max_unavailable(10, None) == 2
    assert get_max_unavailable(50, None) == 5      # 10%
    # explicit int and percent specs (round-down, 0 floors to 1)
    assert get_max_unavailable(20, 3) == 3
    assert get_max_unavailable(20, "25%") == 5
    assert get_max_unavailable(5, "10%") == 1       # 0.5 -> 0 -> floor 1
    # capped at replicas
    assert get_max_unavailable(2, 10) == 2


def test_migration_workload_derived_budgets():
    from koordinator_tpu.descheduler.migration import (
        ControllerFinder, Workload)

    finder = ControllerFinder()
    # 20-replica deployment declaring maxUnavailable 10% -> budget 2
    finder.register(Workload(ref="Deployment/web", expected_replicas=20,
                             max_unavailable="10%", unavailable=1))
    ctl = MigrationController(
        controller_finder=finder,
        evict_fn=lambda j: False,  # keep jobs running to occupy budget
    )
    for i in range(3):
        ctl.submit(MigrationJob(name=f"j{i}", pod=f"p{i}", node=f"n{i}",
                                workload="Deployment/web", create_time=i))
    ctl.reconcile()
    phases = [ctl.jobs[f"j{i}"].phase for i in range(3)]
    # budget 2, one pod already unavailable -> only one migration admitted
    assert phases == [MigrationJobPhase.RUNNING, MigrationJobPhase.PENDING,
                      MigrationJobPhase.PENDING]


def test_migration_unknown_workload_uses_flat_limits():
    from koordinator_tpu.descheduler.migration import ControllerFinder

    ctl = MigrationController(
        controller_finder=ControllerFinder(),   # knows nothing
        evict_fn=lambda j: False,
    )
    for i in range(3):
        ctl.submit(MigrationJob(name=f"j{i}", pod=f"p{i}", node=f"n{i}",
                                workload="Deployment/mystery", create_time=i))
    ctl.reconcile()
    running = sum(j.phase is MigrationJobPhase.RUNNING
                  for j in ctl.jobs.values())
    assert running == 2   # flat default budget
