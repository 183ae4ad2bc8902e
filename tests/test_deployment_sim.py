"""All six binaries composed into one deployment, over their real CLIs.

test_e2e_sim proves the LIBRARY objects stitch into the reference's
flows; this proves the BINARIES do — every component assembled exactly
as `python -m ... <flags>` would, wired over the same sockets a real
deployment uses (SURVEY §2.1): the manager's webhook admits a colocated
pod, the scheduler binary solves it over its listen socket, the
device-daemon's Device CR feeds the scheduler's device manager, the
runtime-proxy binary dispatches container hooks to the koordlet
binary's hook server across TWO RpcServers, and the descheduler binary
runs a round over the resulting cluster view.
"""

import os

import numpy as np
import pytest

from koordinator_tpu.api import crds, extension as ext
from koordinator_tpu.api.qos import QoSClass
from koordinator_tpu.api.resources import resource_vector
from koordinator_tpu.cmd.binaries import MAINS
from koordinator_tpu.koordlet.runtimehooks.server import RemoteHookServer
from koordinator_tpu.koordlet.system.config import make_test_config
from koordinator_tpu.runtimeproxy import HookRequest, HookType
from koordinator_tpu.scheduler.snapshot import NodeSpec, PodSpec
from koordinator_tpu.transport import RpcClient
from koordinator_tpu.transport.services import solve_remote


@pytest.fixture
def deployment(tmp_path):
    cfg = make_test_config(tmp_path)
    # fake sysfs: one TPU accel device for the device daemon to probe
    os.makedirs(os.path.join(cfg.sys_root, "class", "accel", "accel0"),
                exist_ok=True)

    assembled = {}
    clients = []
    try:
        assembled["scheduler"] = MAINS["koord-scheduler"]([
            "--node-capacity", "16",
            "--listen-socket", str(tmp_path / "sched.sock"),
        ])
        assembled["manager"] = MAINS["koord-manager"]([])
        assembled["koordlet"] = MAINS["koordlet"]([
            "--cgroup-root-dir", cfg.cgroup_root,
            "--proc-root-dir", cfg.proc_root,
            "--sys-root-dir", cfg.sys_root,
            # its own var-run: the metric cache is restored from there at
            # start, and the default is one directory for the whole host
            "--var-run-root-dir", cfg.var_run_root,
            "--runtime-hook-server-addr", str(tmp_path / "hooks.sock"),
        ])
        assembled["proxy"] = MAINS["koord-runtime-proxy"]([
            "--hook-server-socket", str(tmp_path / "proxy-hooks.sock"),
        ])
        assembled["descheduler"] = MAINS["koord-descheduler"](
            ["--deschedule-plugins", "podlifetime"],
            pods_fn=lambda: [])
        assembled["device-daemon"] = MAINS["koord-device-daemon"]([
            "--node-name", "n0", "--sys-root-dir", cfg.sys_root,
        ])

        def connect(addr):
            client = RpcClient(addr)
            client.connect()
            clients.append(client)
            return client

        yield assembled, connect, cfg
    finally:
        for client in clients:
            client.close()
        for asm in assembled.values():
            if getattr(asm, "server", None) is not None:
                asm.server.stop()
            stop = getattr(asm.component, "stop", None)
            if callable(stop):
                stop()


def test_six_binaries_one_pod_flow(deployment):
    assembled, connect, cfg = deployment
    scheduler = assembled["scheduler"].component
    manager = assembled["manager"].component

    # --- 1. manager webhook: colocation profile turns a plain spark pod
    # into a BE pod with batch resources
    manager.pod_mutating.profiles.append(crds.ClusterColocationProfile(
        name="colo", pod_selector={"app": "spark"}, qos_class="BE",
        koordinator_priority=5500, scheduler_name="koord-scheduler"))
    pod = {
        "metadata": {"name": "spark-1", "namespace": "default",
                     "labels": {"app": "spark"}},
        "spec": {"containers": [{"name": "m", "resources": {
            "requests": {"cpu": "2", "memory": "4Gi"},
            "limits": {"cpu": "2", "memory": "4Gi"}}}]},
    }
    manager.pod_mutating.mutate(pod)
    assert manager.pod_validating.validate(pod) == []
    requests = pod["spec"]["containers"][0]["resources"]["requests"]
    assert requests[ext.RESOURCE_BATCH_CPU] == 2000

    # --- 2. device daemon probes the fake sysfs into a Device CR; the
    # scheduler's device manager ingests the converted inventory (the
    # same path the Device-CR sync uses: devices.py -> deltasync:507)
    from koordinator_tpu.koordlet.devices import device_infos_to_inventory

    device = assembled["device-daemon"].component.collect()
    assert [d.type for d in device.devices] == ["xpu"]

    scheduler.snapshot.upsert_node(NodeSpec(
        name="n0",
        allocatable=resource_vector({
            "cpu": 16_000, "memory": 32_768,
            ext.RESOURCE_BATCH_CPU: 12_000,
            ext.RESOURCE_BATCH_MEMORY: 24_576,
        })))
    for dev_type, inventory in device_infos_to_inventory(
            list(device.devices)).items():
        scheduler.device_manager.register_node_devices(
            dev_type, "n0", inventory)
    assert scheduler.device_manager.state("xpu") is not None

    # --- 3. the admitted pod schedules over the scheduler binary's
    # listen socket (the sidecar solve path)
    scheduler.enqueue(PodSpec(
        name="spark-1",
        requests=resource_vector({
            ext.RESOURCE_BATCH_CPU: 2000,
            ext.RESOURCE_BATCH_MEMORY: 4 << 10,
        }),
        priority=5500, qos=int(QoSClass.BE)))
    solve_client = connect(assembled["scheduler"].server.path)
    result = solve_remote(solve_client)
    assert result["assignments"] == {"spark-1": "n0"}

    # --- 4. the runtime proxy dispatches the container hooks to the
    # koordlet BINARY's hook server (proxy dispatcher -> RemoteHookServer
    # -> koordlet RpcServer -> RegistryHookServer -> plugins)
    proxy = assembled["proxy"].component
    hook_client = connect(assembled["koordlet"].component.hook_server.path)
    proxy.dispatcher.register(RemoteHookServer(hook_client), list(HookType))
    forwarded = {}
    proxy.backend["CreateContainer"] = (
        lambda req: forwarded.setdefault("create", req))
    request = HookRequest(
        pod_meta={"uid": "spark-1", "name": "spark-1"},
        container_meta={"name": "m", "id": "c1"},
        labels={ext.LABEL_POD_QOS: "BE"},
        cgroup_parent="kubepods/besteffort/podspark-1",
        resources={ext.RESOURCE_BATCH_CPU: 2000,
                   ext.RESOURCE_BATCH_MEMORY: 4 << 30},
    )
    proxy.create_container("c1", request, pod_id="spark-1")
    merged = forwarded["create"].resources
    assert merged["cpu.cfs_quota"] == "200000"   # 2000m over CFS_PERIOD
    assert merged["memory.limit"] == str(4 << 30)
    assert merged["cpu.bvt_warp_ns"] == "-1"     # BE group identity

    # --- 5. the descheduler binary runs a clean round over the cluster
    descheduler = assembled["descheduler"].component
    assert descheduler.run_once() == {"default": 0}


def test_nodemetric_loop_over_the_wire(tmp_path):
    """SURVEY §3.2's report loop in its wire form: the koordlet BINARY
    measures the node and pushes node_usage frames to the scheduler
    BINARY's sidecar, whose in-process binding refreshes the solver's
    usage rows — no Python glue between the two beyond their CLIs."""
    import os
    import time

    from koordinator_tpu.cmd.binaries import (
        main_koord_scheduler,
        main_koordlet,
    )

    sched_asm = main_koord_scheduler([
        "--node-capacity", "8",
        "--listen-socket", str(tmp_path / "sidecar.sock"),
        "--disable-leader-election",
    ])
    cfg = make_test_config(tmp_path)
    os.makedirs(cfg.proc_root, exist_ok=True)

    def write_proc(total_jiffies):
        with open(cfg.proc_path("stat"), "w") as f:
            f.write(f"cpu  {total_jiffies} 0 0 1000 0 0 0 0 0 0\n")
        with open(cfg.proc_path("meminfo"), "w") as f:
            f.write("MemTotal: 16777216 kB\nMemAvailable: 8388608 kB\n"
                    "Cached: 0 kB\nBuffers: 0 kB\nMemFree: 8388608 kB\n")

    koordlet_asm = None
    try:
        # the sidecar must know the node before usage can attach to it
        sched_asm.state_sync.upsert_node(
            "n-metric", resource_vector(cpu=16_000, memory=16_384))

        write_proc(0)
        koordlet_asm = main_koordlet([
            "--cgroup-root-dir", cfg.cgroup_root,
            "--proc-root-dir", cfg.proc_root,
            "--sys-root-dir", cfg.sys_root,
            # its own var-run: the metric cache is restored from there at
            # start, and the default is one directory for the whole host
            "--var-run-root-dir", cfg.var_run_root,
            "--scheduler-sidecar-addr", str(tmp_path / "sidecar.sock"),
            "--node-name", "n-metric",
            "--nodemetric-report-interval-seconds", "0",
        ])
        daemon = koordlet_asm.component
        daemon.tick()                      # first sample (no rate yet)
        time.sleep(0.05)
        write_proc(400)                    # ~cpu burn since last sample
        # reporter rounds run off-thread; tick until the push lands
        snapshot = sched_asm.component.snapshot
        usage_cpu = 0
        deadline = time.monotonic() + 20
        while usage_cpu == 0 and time.monotonic() < deadline:
            daemon.tick()
            time.sleep(0.05)
            snapshot.flush()
            row = snapshot.node_index["n-metric"]
            usage_cpu = int(np.asarray(
                snapshot.state.node_usage)[row][0])
        assert usage_cpu > 0, "pushed usage never reached the solver"
        # and the sync service's stored node carries it for bootstrap
        stored = sched_asm.state_sync.nodes["n-metric"]["arrays"]
        assert int(np.asarray(stored["usage"])[0]) == usage_cpu
        # the colocation-formula inputs ride the same frames
        assert "sys_usage" in stored and "hp_usage" in stored

        # pod-band usage: a running Prod pod's reported usage lands in
        # hp_usage (the colocation formula's HP term) AND prod_usage
        # (loadaware's prod-usage mode input) on the next report
        from koordinator_tpu.api.qos import QoSClass as QC
        from koordinator_tpu.koordlet import metriccache as mcache
        from koordinator_tpu.koordlet.statesinformer import PodMeta

        daemon.states.set_pods([PodMeta(
            uid="prod-1", name="prod-1", namespace="default",
            qos_class=QC.LS, kube_qos="burstable", priority=9_500)])
        now = daemon.clock()
        for dt in (0, 1):
            daemon.metric_cache.append(
                mcache.POD_CPU_USAGE, 1.5,
                labels={"pod_uid": "prod-1"}, ts=now + dt)
            daemon.metric_cache.append(
                mcache.POD_MEMORY_USAGE, 2.0 * (1 << 30),
                labels={"pod_uid": "prod-1"}, ts=now + dt)
        deadline = time.monotonic() + 20
        prod_cpu = 0
        while prod_cpu == 0 and time.monotonic() < deadline:
            daemon.tick()
            time.sleep(0.05)
            stored = sched_asm.state_sync.nodes["n-metric"]["arrays"]
            prod_cpu = int(np.asarray(
                stored.get("prod_usage", np.zeros(1)))[0])
        assert prod_cpu == 1_500, "prod-band usage never reached the wire"
        assert int(np.asarray(stored["hp_usage"])[0]) == 1_500
        assert int(np.asarray(stored["hp_usage"])[1]) == 2_048  # MiB
    finally:
        if koordlet_asm is not None:
            koordlet_asm.component.stop()
        sched_asm.stop()


def test_device_inventory_loop_over_the_wire(tmp_path):
    """The Device-CR report loop in wire form, INCLUDING disappearance:
    the koordlet binary's default sink pushes node_devices frames on
    change, and when every device vanishes it pushes the EMPTY inventory
    so the scheduler's live tensors clear (a skip-when-empty sink would
    leave the node allocatable forever — live-vs-replay divergence)."""
    import shutil
    import time

    from koordinator_tpu.cmd.binaries import (
        main_koord_scheduler,
        main_koordlet,
    )
    from koordinator_tpu.features import KOORDLET_GATES

    sched_asm = main_koord_scheduler([
        "--node-capacity", "8",
        "--listen-socket", str(tmp_path / "devloop.sock"),
        "--disable-leader-election",
    ])
    cfg = make_test_config(tmp_path)
    accel_root = os.path.join(cfg.sys_root, "class", "accel", "accel0")
    os.makedirs(accel_root, exist_ok=True)
    for fn, val in (("uuid", "GPU-0"), ("minor", "0"),
                    ("mem_total", "81920"), ("mem_used", "0"),
                    ("usage_pct", "0"), ("numa_node", "0"),
                    ("health", "1"), ("type", "gpu")):
        with open(os.path.join(accel_root, fn), "w") as f:
            f.write(val)
    os.makedirs(cfg.proc_root, exist_ok=True)
    with open(cfg.proc_path("stat"), "w") as f:
        f.write("cpu  0 0 0 0 0 0 0 0 0 0\n")
    with open(cfg.proc_path("meminfo"), "w") as f:
        f.write("MemTotal: 1024 kB\nMemAvailable: 512 kB\nCached: 0\n")

    koordlet_asm = None
    KOORDLET_GATES.set("Accelerators", True)
    try:
        sched_asm.state_sync.upsert_node(
            "n-dev", resource_vector(cpu=8_000, memory=8_192))
        koordlet_asm = main_koordlet([
            "--cgroup-root-dir", cfg.cgroup_root,
            "--proc-root-dir", cfg.proc_root,
            "--sys-root-dir", cfg.sys_root,
            # its own var-run: the metric cache is restored from there at
            # start, and the default is one directory for the whole host
            "--var-run-root-dir", cfg.var_run_root,
            "--scheduler-sidecar-addr", str(tmp_path / "devloop.sock"),
            "--node-name", "n-dev",
            "--device-report-interval-seconds", "0",
        ])
        daemon = koordlet_asm.component
        from koordinator_tpu.koordlet.statesinformer import NodeInfo

        daemon.states.set_node(NodeInfo(name="n-dev", allocatable={}))
        manager = sched_asm.component.device_manager

        def live_gpus():
            state = manager.state("gpu")
            return 0 if state is None else int(np.asarray(state.valid).sum())

        deadline = time.monotonic() + 20
        while live_gpus() == 0 and time.monotonic() < deadline:
            daemon.tick()
            time.sleep(0.05)
        assert live_gpus() == 1, "device push never reached the solver"

        # a label-only re-upsert on the server clears the node's device
        # inventory (upsert replaces the doc wholesale); the koordlet's
        # HEARTBEAT re-push must restore it — a pure push-on-change
        # cache would strand the node device-less forever
        sched_asm.state_sync.upsert_node(
            "n-dev", resource_vector(cpu=8_000, memory=8_192),
            labels={"zone": "b"})
        assert live_gpus() == 0     # cleared by the re-upsert
        deadline = time.monotonic() + 20
        while live_gpus() == 0 and time.monotonic() < deadline:
            daemon.tick()
            time.sleep(0.05)
        assert live_gpus() == 1, "heartbeat never restored the inventory"

        # the whole accel class vanishes: the sink must push {} so the
        # scheduler clears the type (and the stored doc matches replay)
        shutil.rmtree(os.path.dirname(accel_root))
        deadline = time.monotonic() + 20
        while live_gpus() > 0 and time.monotonic() < deadline:
            daemon.tick()
            time.sleep(0.05)
        assert live_gpus() == 0, "vanished inventory never cleared"
        stored = sched_asm.state_sync.nodes["n-dev"]["doc"]["devices"]
        assert stored == {}
        assert daemon.device_push_failures == 0
    finally:
        KOORDLET_GATES.set("Accelerators", False)
        if koordlet_asm is not None:
            koordlet_asm.component.stop()
        sched_asm.stop()


def test_colocation_loop_binary_to_binary(tmp_path):
    """SURVEY §3.2 closed end to end over real sockets: the koordlet
    BINARY reports node usage to the scheduler
    sidecar, the manager BINARY's noderesource reconcile computes
    batch allocatable from that usage and pushes a node_allocatable
    event back through ITS sidecar client, and the scheduler binary's
    next solve sees the new batch capacity — a BE pod with batch-cpu
    requests goes from unschedulable to scheduled with no Python glue
    between the three beyond their CLIs.  Reference shape:
    slo-controller/noderesource/noderesource_controller.go:71 ->
    plugins/batchresource/plugin.go:188 -> node status patch ->
    scheduler informer."""
    import time

    import jax.numpy as jnp

    from koordinator_tpu.api.resources import ResourceDim
    from koordinator_tpu.cmd.binaries import (
        main_koord_manager,
        main_koord_scheduler,
        main_koordlet,
    )

    sched_asm = main_koord_scheduler([
        "--node-capacity", "8",
        "--listen-socket", str(tmp_path / "colo.sock"),
        "--disable-leader-election",
    ])
    cfg = make_test_config(tmp_path)
    os.makedirs(cfg.proc_root, exist_ok=True)

    def write_proc(total_jiffies):
        with open(cfg.proc_path("stat"), "w") as f:
            f.write(f"cpu  {total_jiffies} 0 0 1000 0 0 0 0 0 0\n")
        with open(cfg.proc_path("meminfo"), "w") as f:
            f.write("MemTotal: 16777216 kB\nMemAvailable: 12582912 kB\n"
                    "Cached: 0 kB\nBuffers: 0 kB\nMemFree: 12582912 kB\n")

    koordlet_asm = manager_asm = None
    try:
        scheduler = sched_asm.component
        # the node registers with BASE capacity only — no batch dims yet
        sched_asm.state_sync.upsert_node(
            "n-colo", resource_vector(cpu=16_000, memory=16_384))

        # a BE pod requesting batch resources: unschedulable while no
        # node advertises batch capacity
        sched_asm.state_sync.add_pod(
            "be-1", resource_vector({
                ext.RESOURCE_BATCH_CPU: 2_000,
                ext.RESOURCE_BATCH_MEMORY: 1_024}),
            priority=5500, qos=int(QoSClass.BE))
        # the first solve of a shape compiles in-line: the client's
        # budget covers a compile on a host busy with five other test
        # workers (the default 10 s did not)
        solve_client = RpcClient(sched_asm.server.path, timeout=300.0)
        solve_client.connect()
        result = solve_remote(solve_client)
        assert "be-1" in result["failures"], result

        # koordlet binary reports usage over the wire.  The collector's
        # cpu rate is jiffies-delta / clock-delta: the koordlet and the
        # manager run on a clock this test steps (one second a tick,
        # starting at the wall's now, so reports stay fresh to the
        # scheduler), and a reading is then a pure function of the
        # jiffies written, whatever the machine's load does to the wall
        # between two ticks — the BE pod must be gated on BATCH
        # CAPACITY, not on usage pressure
        now = [time.time()]

        def clock() -> float:
            return now[0]

        write_proc(0)
        koordlet_asm = main_koordlet([
            "--cgroup-root-dir", cfg.cgroup_root,
            "--proc-root-dir", cfg.proc_root,
            "--sys-root-dir", cfg.sys_root,
            # its own var-run: the metric cache is restored from there at
            # start, and the default is one directory for the whole host
            "--var-run-root-dir", cfg.var_run_root,
            "--scheduler-sidecar-addr", str(tmp_path / "colo.sock"),
            "--node-name", "n-colo",
            "--nodemetric-report-interval-seconds", "0",
        ], clock=clock)
        daemon = koordlet_asm.component
        daemon.tick()
        # 40 jiffies over exactly one second: 0.4 cores of 16
        write_proc(40)
        # bounded by the loop's own steps, not by seconds: a tick
        # collects, its report goes out on a thread, and a slow host
        # only makes a step longer
        for _ in range(400):
            now[0] += 1.0
            daemon.tick()
            time.sleep(0.05)
            stored = sched_asm.state_sync.nodes["n-colo"]["arrays"]
            if int(np.asarray(stored.get(
                    "usage", np.zeros(1)))[0]) > 0:
                break
        else:
            raise AssertionError("koordlet usage never reached the sidecar")

        # manager binary: watches the same sidecar, reconciles, pushes
        manager_asm = main_koord_manager([
            "--scheduler-sidecar-addr", str(tmp_path / "colo.sock"),
        ], clock=clock)
        manager = manager_asm.component
        # the sidecar client dials lazily: the first tick bootstraps the
        # watch and reconciles; the test keeps the whole loop ticking
        # (report, reconcile, push) until the scheduler's
        # device-resident allocatable carries the capacity.
        row = scheduler.snapshot.node_index["n-colo"]
        batch_cpu = 0
        for _ in range(300):
            if batch_cpu >= 2_000:
                break
            now[0] += 1.0
            daemon.tick()
            manager.colocation_loop.tick()
            scheduler.snapshot.flush()
            batch_cpu = int(np.asarray(
                scheduler.snapshot.state.node_allocatable
            )[row][int(ResourceDim.BATCH_CPU)])
            time.sleep(0.1)
        assert manager.colocation_loop.connect_failures == 0
        assert batch_cpu >= 2_000, (
            f"batch capacity {batch_cpu} too small for the BE pod "
            f"(pushes={manager.colocation_loop.push_failures})")
        # one node: every patch went out as a run-form frame of one, and
        # the manager's watch took its echo before the push returned
        from koordinator_tpu import metrics

        frames = metrics.colocation_push_frames_total.value()
        assert frames >= 1
        assert frames == (metrics.colocation_patches_total.value()
                          + manager.colocation_loop.push_failures)
        assert manager.sync_binding.nodes["n-colo"].allocatable[
            int(ResourceDim.BATCH_CPU)] == batch_cpu

        # and the BE pod now schedules — over the same solve socket
        result = solve_remote(solve_client)
        if result["assignments"].get("be-1") != "n-colo":
            # what the sidecar holds of the node, in full (pytest cuts a
            # long assertion message)
            for key, value in sched_asm.state_sync.nodes["n-colo"][
                    "arrays"].items():
                print("n-colo", key, np.asarray(value).tolist())
        assert result["assignments"].get("be-1") == "n-colo", result
        solve_client.close()
    finally:
        if koordlet_asm is not None:
            koordlet_asm.component.stop()
        if manager_asm is not None:
            manager_asm.component.stop()
        sched_asm.stop()
