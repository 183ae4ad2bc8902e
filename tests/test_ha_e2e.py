"""Cross-process HA, end to end: two real scheduler PROCESSES sync state
over the wire protocol, contend one lease, and the survivor keeps
scheduling after the leader is SIGKILLed.

This is the deployment story the reference runs on the apiserver
(leader-elected koord-scheduler replicas, informer-fed, Lease locks): here
the state server (deltasync) plays the apiserver, lease frames carry the
lock, and rounds are leader-gated inside each Scheduler.  Binds surface
through each process's status file; the test plays the apiserver's part of
the bind wash by removing bound pods from the shared state so both
replicas converge.
"""

import textwrap
import time

from koordinator_tpu.api.resources import resource_vector
from koordinator_tpu.ha import LeaseService
from koordinator_tpu.transport.channel import RpcServer
from koordinator_tpu.transport.deltasync import StateSyncService

from tests.proc_helpers import kill_all, spawn_replicas, wait_for

#: long enough that no post-warmup pause (GC, loaded CI core) outlives the
#: lease and flips leadership mid-test; failover after SIGKILL waits this out
LEASE_SECONDS = 20.0

REPLICA = textwrap.dedent("""
    import sys, time
    sock, ident, status, lease_s = (
        sys.argv[1], sys.argv[2], sys.argv[3], float(sys.argv[4]))

    from koordinator_tpu.api.resources import resource_vector
    from koordinator_tpu.ha import LeaderElector, RemoteLeaseStore
    from koordinator_tpu.scheduler.scheduler import Scheduler
    from koordinator_tpu.scheduler.snapshot import (
        ClusterSnapshot,
        NodeSpec,
        PodSpec,
    )
    from koordinator_tpu.transport.channel import RpcClient
    from koordinator_tpu.transport.deltasync import (
        SchedulerBinding,
        StateSyncClient,
    )

    ready = [False]

    def bind_fn(pod, node):
        if not ready[0]:
            return               # warmup binds stay private
        with open(status, "a") as f:
            f.write(f"BIND {pod} {node}\\n")

    snap = ClusterSnapshot(capacity=16)
    sched = Scheduler(snap, bind_fn=bind_fn)
    # WARMUP before contending the lease: the first round jit-compiles the
    # solve; on a loaded single-core CI box that pause can exceed the
    # lease and flip leadership mid-test.  The wire bootstrap below resets
    # all scheduler state, washing the dummy binds away.
    for i in range(2):
        snap.upsert_node(NodeSpec(
            name=f"warm-n{i}",
            allocatable=resource_vector(cpu=16_000, memory=65_536)))
    for i in range(3):
        sched.enqueue(PodSpec(name=f"warm-p{i}",
                              requests=resource_vector(cpu=1_000,
                                                       memory=1_024)))
    sched.schedule_round()

    sync = StateSyncClient(SchedulerBinding(sched))
    client = RpcClient(sock, on_push=sync.on_push)
    client.connect()
    sync.bootstrap(client)
    # wall clock: contenders in different processes share a clock domain
    sched.elector = LeaderElector(
        RemoteLeaseStore(client), "koord-scheduler", ident,
        lease_duration=lease_s, clock=time.time)
    ready[0] = True
    with open(status, "a") as f:
        f.write("READY\\n")
    while True:
        try:
            sched.schedule_round()   # leader-gated internally
        except Exception as e:
            with open(status, "a") as f:
                f.write(f"ERROR {e!r}\\n")
        time.sleep(0.1)
""")


def _binds(path):
    out = []
    for line in path.read_text().splitlines():
        if line.startswith("BIND "):
            _, pod, node = line.split()
            out.append((pod, node))
    return out


def test_two_scheduler_processes_failover_and_keep_scheduling(tmp_path):
    sock = str(tmp_path / "state.sock")
    server = RpcServer(sock)
    service = StateSyncService()
    service.attach(server)
    LeaseService().attach(server)
    server.start()

    script = tmp_path / "replica.py"
    script.write_text(REPLICA)
    status = {i: tmp_path / f"status-{i}" for i in ("a", "b")}
    for f in status.values():
        f.write_text("")

    for i in range(2):
        service.upsert_node(
            f"n{i}", resource_vector(cpu=16_000, memory=65_536))

    procs, errs = spawn_replicas(
        script,
        {i: [sock, i, str(status[i]), str(LEASE_SECONDS)]
         for i in ("a", "b")},
        tmp_path)
    try:
        # wait for both replicas to finish warmup + bootstrap, so neither
        # contends the lease while still compiling
        wait_for(
            lambda: all("READY" in status[i].read_text()
                        for i in ("a", "b")),
            procs, errs, 240, "replica warmup")

        # phase 1: pods for the first leader
        for i in range(3):
            service.add_pod(f"p{i}", resource_vector(cpu=1_000,
                                                     memory=1_024))

        def all_binds():
            return {i: _binds(status[i]) for i in ("a", "b")}

        def phase1_done():
            bound = {p for v in all_binds().values() for (p, _) in v}
            return {"p0", "p1", "p2"} <= bound

        wait_for(phase1_done, procs, errs, 120, "phase-1 binds")
        leader = "a" if _binds(status["a"]) else "b"
        # exactly ONE replica schedules while the lease is held
        standby = "b" if leader == "a" else "a"
        assert not _binds(status[standby]), \
            "standby replica scheduled while the leader held the lease"
        # apiserver wash: bound pods leave the shared state
        for p, _ in _binds(status[leader]):
            service.remove_pod(p)

        procs[leader].kill()     # SIGKILL: no voluntary lease release
        procs[leader].wait(timeout=10)
        live = {standby: procs[standby]}

        # phase 2: new pods arrive; the standby must wait out the lease,
        # take over, and bind
        for i in range(3, 6):
            service.add_pod(f"p{i}", resource_vector(cpu=1_000,
                                                     memory=1_024))
        wait_for(
            lambda: {"p3", "p4", "p5"} <= {
                p for (p, _) in _binds(status[standby])},
            live, errs, 180, "standby takeover binds")
        got = {p for (p, _) in _binds(status[standby])}
        # no pod was ever bound by both replicas
        dup = {p for (p, _) in _binds(status[leader])} & got
        assert not dup, f"pods double-bound across replicas: {dup}"
    finally:
        kill_all(procs)
        server.stop()
