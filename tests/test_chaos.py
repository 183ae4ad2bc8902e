"""Chaos soak: scheduler sidecar + manager + koordlet-style feeder over
real unix sockets under a SEEDED fault schedule (transport/faults.py —
connection severs, mid-write truncation, push drop/delay/duplication/
reordering, slow-drip reads, connect refusals), asserting the three
acceptance invariants:

1. **No overcommit, ever** — an oracle re-checks every acceptance at
   bind time: the host-side sum of bound pods on the node (including
   the new one) must fit the node's allocatable on every dimension.
2. **Reconvergence after heal** — once the injector heals, every pod
   (prod AND BE/batch-dim) reaches a binding within bounded rounds, the
   manager's watch view catches back up to the service rv, and the
   scheduler leaves degraded mode.
3. **No thread/fd growth** — reconnect storms must not accumulate
   reader/sender threads or leak sockets (satellite: RpcClient.close
   joins its reader).

Marked ``chaos`` AND ``slow``: tier-1's ``-m "not slow"`` keeps it out
of CI; run it with ``pytest -m chaos`` or sweep seed windows with
``SOAK_CHAOS=1 tools/soak.sh`` (the failing seed base is printed for
exact replay via ``KOORD_CHAOS_SEED_BASE``).
"""

import os
import threading
import time

import numpy as np
import pytest

from koordinator_tpu.api.qos import QoSClass
from koordinator_tpu.api.resources import NUM_RESOURCE_DIMS, resource_vector
from koordinator_tpu.cmd.binaries import ReconnectingSidecarClient
from koordinator_tpu.manager.colocation_loop import (
    ColocationLoop,
    ManagerSyncBinding,
    sidecar_push,
)
from koordinator_tpu.manager.noderesource_controller import (
    NodeResourceController,
)
from koordinator_tpu.ops.assignment import ScoringConfig
from koordinator_tpu.scheduler import ClusterSnapshot, Scheduler
from koordinator_tpu.transport import (
    FaultConfig,
    FaultInjector,
    RpcError,
    RpcRemoteError,
    RpcServer,
    StateSyncClient,
    StateSyncService,
)
from koordinator_tpu.transport.deltasync import SchedulerBinding
from koordinator_tpu.transport.retry import RetryPolicy
from koordinator_tpu.transport.services import SolveService
from koordinator_tpu.transport.wire import FrameType

pytestmark = [pytest.mark.chaos, pytest.mark.slow]

R = NUM_RESOURCE_DIMS
NODES = 4
PROD_PODS = 8
BE_PODS = 4


def chaos_seeds():
    """Seed window, env-steerable exactly like conftest.prop_seeds — the
    soak harness sweeps fresh windows and prints the base on failure."""
    base = int(os.environ.get("KOORD_CHAOS_SEED_BASE", "0"))
    count = int(os.environ.get("KOORD_CHAOS_SEED_COUNT", "0") or 0) or 5
    return list(range(base, base + count))


#: fast-probing retry policy so a ~15s soak sees many breaker cycles
FAST_RETRY = RetryPolicy(initial_backoff_s=0.02, max_backoff_s=0.3,
                         multiplier=2.0, jitter="equal")

CHAOS = FaultConfig(
    connect_refuse_p=0.10,
    send_sever_p=0.01,
    send_truncate_p=0.005,
    push_drop_p=0.05,
    push_delay_p=0.05,
    push_delay_ms=5.0,
    push_duplicate_p=0.05,
    push_reorder_p=0.05,
    read_drip_p=0.02,
    read_drip_ms=2.0,
)


def _counts():
    return threading.active_count(), len(os.listdir("/proc/self/fd"))


def wait_until(pred, timeout, what):
    deadline = time.monotonic() + timeout
    while not pred() and time.monotonic() < deadline:
        time.sleep(0.02)
    assert pred(), f"timed out waiting for {what}"


class Oracle:
    """Re-checks every acceptance the moment it is made (bind_fn runs
    under the round lock, so the host sums and the snapshot agree)."""

    def __init__(self):
        self.sched = None
        self.violations = []
        self.accepted = 0

    def __call__(self, pod_name, node_name):
        self.accepted += 1
        sched = self.sched
        spec = sched.snapshot.node_specs.get(node_name)
        if spec is None:
            self.violations.append(f"{pod_name} bound to unknown node "
                                   f"{node_name}")
            return
        total = np.zeros(R, np.int64)
        for bp in sched.bound.values():
            if bp.node == node_name:
                total += bp.requests.astype(np.int64)
        alloc = spec.allocatable.astype(np.int64)
        if not np.all(total <= alloc):
            self.violations.append(
                f"overcommit on {node_name} accepting {pod_name}: "
                f"bound={total.tolist()} allocatable={alloc.tolist()}")


def node_usage_arrays():
    return {
        "usage": np.asarray(resource_vector(cpu=2_000, memory=4_096),
                            np.int32),
        "sys_usage": np.asarray(resource_vector(cpu=500, memory=512),
                                np.int32),
        "hp_usage": np.asarray(resource_vector(cpu=3_000, memory=2_048),
                               np.int32),
        "hp_request": np.asarray(resource_vector(cpu=3_000, memory=2_048),
                                 np.int32),
        "hp_max_used_req": np.asarray(
            resource_vector(cpu=3_000, memory=2_048), np.int32),
    }


@pytest.mark.parametrize("seed", chaos_seeds())
def test_chaos_soak(seed, tmp_path):
    inj = FaultInjector(seed=seed, config=CHAOS)
    inj.enabled = False                      # clean warmup first
    sock = str(tmp_path / f"chaos-{seed}.sock")

    # -- sidecar: server + sync service + in-process scheduler binding
    import jax.numpy as jnp  # deferred per the marker-audit convention

    oracle = Oracle()
    cfg = ScoringConfig.default().replace(
        usage_thresholds=jnp.zeros(R, jnp.int32),
        estimator_defaults=jnp.zeros(R, jnp.int32))
    sched = Scheduler(ClusterSnapshot(capacity=16), config=cfg,
                      bind_fn=oracle, staleness_threshold_sec=2.0)
    oracle.sched = sched
    server = RpcServer(sock, faults=inj)
    service = StateSyncService(retention=64)
    service.attach(server)
    service.attach_binding(SchedulerBinding(sched))
    solve_service = SolveService(sched)
    solve_service.attach(server)
    server.start()

    # -- koordlet-style feeder (node heartbeats) + workload pusher
    feeder = ReconnectingSidecarClient(sock, retry_policy=FAST_RETRY,
                                       faults=inj, timeout=3.0)

    # -- manager: watch view + colocation loop pushing batch allocatable
    binding = ManagerSyncBinding()
    sync = StateSyncClient(binding)

    def bootstrap_watch(client):
        sync.bind_client(client)
        sync.bootstrap(client)

    mgr_client = ReconnectingSidecarClient(
        sock, on_push=sync.on_push, on_connect=bootstrap_watch,
        retry_policy=FAST_RETRY, faults=inj, timeout=3.0)

    loop = ColocationLoop(NodeResourceController(), binding,
                          sidecar_push(mgr_client),
                          ensure_fn=mgr_client.ensure)

    # -- solver driver: long transport timeout, per-call deadline_ms
    # bounds the steady-state waits (and lets the warmup ride out jit
    # compilation)
    solver = ReconnectingSidecarClient(sock, retry_policy=FAST_RETRY,
                                       faults=inj, timeout=120.0)

    #: warm-0 schedules during the (fault-free) warmup so the solve is
    #: compiled and the solver connection live before the baseline
    #: thread/fd counts are taken; everything else arrives UNDER chaos
    pods = (
        [("warm-0", resource_vector(cpu=1_000, memory=1_024), 0, 1000)]
        + [(f"prod-{i}", resource_vector(cpu=1_000, memory=1_024), 0, 1000)
           for i in range(PROD_PODS)]
        + [(f"be-{i}", resource_vector(batch_cpu=500, batch_memory=256),
            int(QoSClass.BE), 0)
           for i in range(BE_PODS)]
    )
    pushed_pods: set[str] = set()

    def push_pending_pods(client):
        for name, req, qos, prio in pods:
            if name in pushed_pods:
                continue
            try:
                client.call(FrameType.STATE_PUSH,
                            {"kind": "pod_add", "name": name,
                             "qos": qos, "priority": prio},
                            {"requests": np.asarray(req, np.int32)})
                pushed_pods.add(name)
            except (RpcError, RpcRemoteError, OSError):
                return                       # retry the rest next cycle

    def one_cycle():
        """One control-plane beat with every error swallowed the way the
        real binaries swallow them (count-and-retry-next-tick)."""
        for n in range(NODES):
            try:
                feeder.call(FrameType.STATE_PUSH,
                            {"kind": "node_usage", "name": f"n{n}",
                             "usage_time": time.time()},
                            node_usage_arrays())
            except (RpcError, RpcRemoteError, OSError):
                pass
        push_pending_pods(feeder)
        loop.tick()
        try:
            solver.call(FrameType.SOLVE_REQUEST, {}, deadline_ms=3_000)
        except (RpcError, RpcRemoteError, OSError):
            pass
        assert not oracle.violations, oracle.violations[:3]

    try:
        # ---- warmup (no faults): register nodes, compile the solve,
        # establish every steady-state connection BEFORE the baseline
        for n in range(NODES):
            feeder.call(FrameType.STATE_PUSH,
                        {"kind": "node_upsert", "name": f"n{n}"},
                        {"allocatable": np.asarray(
                            resource_vector(cpu=16_000, memory=16_384),
                            np.int32)})
        feeder.call(FrameType.STATE_PUSH,
                    {"kind": "pod_add", "name": "warm-0", "priority": 1000},
                    {"requests": np.asarray(
                        resource_vector(cpu=1_000, memory=1_024),
                        np.int32)})
        pushed_pods.add("warm-0")
        loop.tick()
        # generous deadline: the first solve pays jit compilation, and a
        # client-side timeout here would close the solver connection and
        # skew the thread/fd baseline
        solver.call(FrameType.SOLVE_REQUEST, {}, deadline_ms=120_000)
        with sched.lock:
            assert sched.bound, "warmup pod never scheduled"
        wait_until(lambda: sync.rv >= 0, 5, "manager bootstrap")
        base_threads, base_fds = _counts()

        # ---- chaos phase
        inj.enabled = True
        t_end = time.monotonic() + 8.0
        while time.monotonic() < t_end:
            one_cycle()
            time.sleep(0.01)
        assert sum(inj.injected.values()) > 0, (
            "the fault schedule never fired — the soak proved nothing")

        # ---- heal: the system must reconverge to the full fixpoint
        inj.heal()
        deadline = time.monotonic() + 30.0
        want = {name for name, *_ in pods}
        while time.monotonic() < deadline:
            one_cycle()
            with sched.lock:
                done = (set(sched.bound) == want and not sched.degraded)
            if done and sync.rv == service.rv:
                break
            time.sleep(0.02)
        with sched.lock:
            assert set(sched.bound) == want, (
                f"no-fault fixpoint not reached: "
                f"missing={sorted(want - set(sched.bound))} "
                f"pending={sorted(sched.pending)} "
                f"degraded={sched.degraded}")
            assert not sched.degraded
        assert sync.rv == service.rv, "manager watch never caught up"
        assert not oracle.violations, oracle.violations[:3]
        assert oracle.accepted >= len(pods)
        # the loop's patches left in run-form frames, lost ones were sent
        # again: what the manager last stamped on a node is what the
        # sidecar holds of it
        from koordinator_tpu import metrics
        from koordinator_tpu.api.resources import ResourceDim

        assert 0 < metrics.colocation_push_frames_total.value() <= (
            metrics.colocation_patches_total.value()
            + metrics.colocation_push_failures_total.value())
        assert len(binding.records) == NODES
        for name, record in binding.records.items():
            stored = service.nodes[name]["arrays"]["allocatable"]
            assert int(stored[ResourceDim.BATCH_CPU]) == \
                record.last_batch_cpu, name

        # ---- no thread/fd growth vs the warmed-up baseline
        def settled():
            t, f = _counts()
            return t <= base_threads and f <= base_fds + 2

        wait_until(settled, 10,
                   f"thread/fd settle (base={base_threads}t/{base_fds}fd, "
                   f"now={_counts()})")
    finally:
        feeder.close()
        mgr_client.close()
        solver.close()
        server.stop()
