"""Device collectors (gpu/rdma/xpu parity) and the resctrl/tc/terwayqos
runtime hooks — the tail of the koordlet coverage matrix.

Reference anchors: pkg/koordlet/metricsadvisor/devices/{gpu,rdma,xpu},
pkg/koordlet/runtimehooks/hooks/{resctrl,tc,terwayqos}.
"""

import json
import os

import pytest

from koordinator_tpu.api import extension as ext
from koordinator_tpu.api.qos import QoSClass
from koordinator_tpu.features import KOORDLET_GATES, RUNTIMEHOOK_GATES
from koordinator_tpu.koordlet import metriccache as mc
from koordinator_tpu.koordlet.devices import (
    AcceleratorCollector,
    RdmaCollector,
    XpuCollector,
)
from koordinator_tpu.koordlet.metricsadvisor import _Deps
from koordinator_tpu.koordlet.runtimehooks.plugins import (
    TC_CLASSID_HIGH,
    TC_CLASSID_LOW,
    TC_CLASSID_MID,
    ResctrlHook,
    ResctrlUpdater,
    TCNetworkQoS,
    TerwayQoS,
    tc_setup_commands,
)
from koordinator_tpu.koordlet.runtimehooks.protocol import PodContext
from koordinator_tpu.koordlet.statesinformer import PodMeta, StatesInformer
from koordinator_tpu.koordlet.system import cgroup as cg
from koordinator_tpu.koordlet.system.config import make_test_config


@pytest.fixture
def cfg(tmp_path):
    return make_test_config(tmp_path)


def make_deps(cfg):
    return _Deps(StatesInformer(), mc.MetricCache(), cfg, lambda: 100.0)


def pod(qos=QoSClass.BE, annotations=None):
    return PodMeta(
        uid="pod-1", name="pod-1", namespace="default", qos_class=qos,
        kube_qos="besteffort" if qos.is_best_effort else "burstable",
        annotations=annotations or {},
    )


def run_hook(hook, p):
    ctx = PodContext(pod=p, cgroup_dir="kubepods/pod-1")
    hook(ctx)
    return ctx.response


def fake_accel_device(cfg, name="accel0", **fields):
    root = os.path.join(cfg.sys_root, "class", "accel", name)
    os.makedirs(root, exist_ok=True)
    defaults = dict(uuid=f"GPU-{name}", minor="0", type="gpu",
                    usage_pct="37.5", mem_used="1024", mem_total="8192",
                    numa_node="1", busid="0000:3b:00.0", health="1")
    defaults.update(fields)
    for fn, val in defaults.items():
        with open(os.path.join(root, fn), "w") as f:
            f.write(str(val))


class TestAcceleratorCollector:
    def _fake_device(self, cfg, name="accel0", **fields):
        fake_accel_device(cfg, name, **fields)

    def test_samples_and_device_infos(self, cfg):
        self._fake_device(cfg, "accel0", minor="0")
        self._fake_device(cfg, "accel1", minor="1", health="0",
                          usage_pct="80")
        deps = make_deps(cfg)
        col = AcceleratorCollector(deps)
        KOORDLET_GATES.set("Accelerators", True)
        try:
            assert col.enabled()
            col.collect()
        finally:
            KOORDLET_GATES.set("Accelerators", False)
        res = deps.cache.query(mc.ACCEL_CORE_USAGE,
                               {"minor": "0", "uuid": "GPU-accel0",
                                "type": "gpu"}, end=200.0)
        assert list(res.values) == [37.5]
        infos = col.device_infos()
        assert [d.uuid for d in infos] == ["GPU-accel0", "GPU-accel1"]
        assert infos[0].health and not infos[1].health
        assert infos[0].numa_node == 1
        assert infos[0].resources["gpu-memory"] == 8192

    def test_gate_and_missing_sysfs_disable(self, cfg):
        col = AcceleratorCollector(make_deps(cfg))
        KOORDLET_GATES.set("Accelerators", True)
        try:
            assert not col.enabled()      # no sysfs dir
        finally:
            KOORDLET_GATES.set("Accelerators", False)
        self._fake_device(cfg, "accel0")
        assert not col.enabled()          # gate off


class TestRdmaCollector:
    def test_inventory_with_port_state(self, cfg):
        base = os.path.join(cfg.sys_root, "class", "infiniband", "mlx5_0")
        os.makedirs(os.path.join(base, "ports", "1"), exist_ok=True)
        with open(os.path.join(base, "node_guid"), "w") as f:
            f.write("0c42:a103:0065:2b8a")
        with open(os.path.join(base, "ports", "1", "state"), "w") as f:
            f.write("4: ACTIVE")
        down = os.path.join(cfg.sys_root, "class", "infiniband", "mlx5_1")
        os.makedirs(os.path.join(down, "ports", "1"), exist_ok=True)
        with open(os.path.join(down, "ports", "1", "state"), "w") as f:
            f.write("1: DOWN")

        infos = RdmaCollector(make_deps(cfg)).device_infos()
        by_uuid = {d.uuid: d for d in infos}
        assert by_uuid["0c42:a103:0065:2b8a"].health
        assert not by_uuid["mlx5_1"].health
        assert all(d.type == "rdma" for d in infos)


class TestXpuCollector:
    def test_vendor_json_inventory(self, cfg):
        root = os.path.join(cfg.var_run_root, "xpu-device-infos")
        os.makedirs(root, exist_ok=True)
        with open(os.path.join(root, "dev0.json"), "w") as f:
            json.dump({"uuid": "XPU-0", "minor": 0, "healthy": True,
                       "vendor": "acme", "model": "x100",
                       "numaNode": 0, "busID": "0000:17:00.0",
                       "resources": {"xpu-core": 100,
                                     "xpu-memory": 65536}}, f)
        with open(os.path.join(root, "broken.json"), "w") as f:
            f.write("{not json")

        infos = XpuCollector(make_deps(cfg)).device_infos()
        assert len(infos) == 1            # broken file skipped, not fatal
        d = infos[0]
        assert d.uuid == "XPU-0" and d.labels["vendor"] == "acme"
        assert d.resources["xpu-memory"] == 65536


class TestResctrlHook:
    @pytest.fixture(autouse=True)
    def gate(self):
        RUNTIMEHOOK_GATES.set("Resctrl", True)
        yield
        RUNTIMEHOOK_GATES.set("Resctrl", False)

    def test_annotated_pod_gets_private_group(self):
        p = pod(qos=QoSClass.LS, annotations={
            ext.ANNOTATION_RESCTRL: json.dumps({"l3": 50, "mb": 40})})
        resp = run_hook(ResctrlHook(num_ways=20), p)
        assert resp.resctrl_group == "koord-pod-pod-1"
        # 50% of 20 ways = 10 low bits set
        assert resp.resctrl_schemata == f"L3:0={(1 << 10) - 1:x}\nMB:0=40\n"

    def test_unannotated_pod_joins_qos_group(self):
        assert run_hook(ResctrlHook(), pod(QoSClass.BE)).resctrl_group == "BE"
        assert run_hook(ResctrlHook(), pod(QoSClass.LSR)).resctrl_group == "LSR"
        assert run_hook(ResctrlHook(), pod(QoSClass.LS)).resctrl_group == "LS"

    def test_updater_programs_fake_resctrl_fs(self, cfg):
        p = pod(annotations={
            ext.ANNOTATION_RESCTRL: json.dumps({"l3": 100})})
        resp = run_hook(ResctrlHook(num_ways=4), p)
        updater = ResctrlUpdater(cfg)
        updater.apply(resp, pids=[1234])
        gdir = updater.fs.group_dir("koord-pod-pod-1")
        assert open(os.path.join(gdir, "schemata")).read() == "L3:0=f\n"
        assert "1234" in open(os.path.join(gdir, "tasks")).read()
        updater.remove_group("pod-1")
        assert not os.path.isdir(gdir)


class TestTCNetworkQoS:
    @pytest.fixture(autouse=True)
    def gate(self):
        RUNTIMEHOOK_GATES.set("TCNetworkQoS", True)
        yield
        RUNTIMEHOOK_GATES.set("TCNetworkQoS", False)

    def test_classid_per_tier(self):
        hook = TCNetworkQoS()
        key = cg.NET_CLS_CLASSID.name
        assert run_hook(hook, pod(QoSClass.BE)).cgroup_values[
            key] == str(TC_CLASSID_LOW)
        assert run_hook(hook, pod(QoSClass.LSR)).cgroup_values[
            key] == str(TC_CLASSID_HIGH)
        assert run_hook(hook, pod(QoSClass.LS)).cgroup_values[
            key] == str(TC_CLASSID_HIGH)
        assert run_hook(hook, pod(QoSClass.NONE)).cgroup_values[
            key] == str(TC_CLASSID_MID)

    def test_setup_commands_htb_plan(self):
        cmds = tc_setup_commands("eth0", 10_000)
        assert cmds[0][:4] == ["tc", "qdisc", "add", "dev"]
        assert "htb" in cmds[0]
        # guaranteed rates split the line rate, ceils borrow up to it
        assert "4000mbit" in cmds[1] and "10000mbit" in cmds[1]
        assert "3000mbit" in cmds[2] and "3000mbit" in cmds[3]

    def test_gate_off_is_noop(self):
        RUNTIMEHOOK_GATES.set("TCNetworkQoS", False)
        assert cg.NET_CLS_CLASSID.name not in run_hook(
            TCNetworkQoS(), pod(QoSClass.BE)).cgroup_values


class TestTerwayQoS:
    @pytest.fixture(autouse=True)
    def gate(self):
        RUNTIMEHOOK_GATES.set("TerwayQoS", True)
        yield
        RUNTIMEHOOK_GATES.set("TerwayQoS", False)

    def test_writes_and_removes_bandwidth_file(self, cfg):
        hook = TerwayQoS(cfg)
        p = pod(qos=QoSClass.BE, annotations={
            ext.ANNOTATION_NETWORK_QOS: json.dumps(
                {"ingressBps": 1_000_000, "egressBps": 2_000_000})})
        run_hook(hook, p)
        path = os.path.join(cfg.var_run_root, "terway-qos", "pod-1.json")
        data = json.load(open(path))
        assert data == {"podUID": "pod-1", "ingressBps": 1_000_000,
                        "egressBps": 2_000_000, "prio": 2}
        hook.remove("pod-1")
        assert not os.path.exists(path)

    def test_no_annotation_no_file(self, cfg):
        hook = TerwayQoS(cfg)
        run_hook(hook, pod(QoSClass.LS))
        assert not os.path.exists(os.path.join(
            cfg.var_run_root, "terway-qos", "pod-1.json"))


class TestDeviceInventoryBridge:
    def test_device_infos_to_inventory_round_trip(self):
        from koordinator_tpu.api import crds
        from koordinator_tpu.koordlet.devices import (
            device_infos_to_inventory,
        )
        from koordinator_tpu.scheduler.device_manager import DeviceManager

        infos = [
            crds.DeviceInfo(type="gpu", minor=0, health=True, numa_node=0,
                            resources={"gpu-core": 100,
                                       "gpu-memory": 81_920}),
            crds.DeviceInfo(type="gpu", minor=2, health=True, numa_node=1,
                            resources={"gpu-core": 100,
                                       "gpu-memory": 81_920}),
            crds.DeviceInfo(type="gpu", minor=1, health=False, numa_node=0,
                            resources={"gpu-core": 100,
                                       "gpu-memory": 81_920}),
            crds.DeviceInfo(type="rdma", minor=0,
                            resources={"rdma-core": 100}),
        ]
        inv = device_infos_to_inventory(infos)
        assert len(inv["gpu"]) == 3
        assert inv["gpu"][1] == {"core": 0, "memory": 0, "group": 0}  # sick
        assert inv["gpu"][2]["group"] == 1
        assert inv["rdma"][0]["core"] == 100

        mgr = DeviceManager()
        mgr.register_node_devices("gpu", "n0", inv["gpu"])
        # only the two healthy GPUs allocate
        assert mgr.allocate("gpu", "n0", "p", core=200) is not None
        assert mgr.allocate("gpu", "n0", "q", core=100) is None


class TestResctrlReconcile:
    def test_reconciler_applies_and_removes_resctrl(self, cfg):
        """The daemon path: annotated pod gets its ctrl group programmed at
        reconcile; the group is removed when the pod leaves the node."""
        from koordinator_tpu.koordlet.resourceexecutor import (
            ResourceUpdateExecutor,
        )
        from koordinator_tpu.koordlet.runtimehooks.hooks import HookRegistry
        from koordinator_tpu.koordlet.runtimehooks.plugins import (
            ResctrlUpdater,
            register_default_hooks,
        )
        from koordinator_tpu.koordlet.runtimehooks.reconciler import (
            Reconciler,
        )
        from koordinator_tpu.api import crds

        RUNTIMEHOOK_GATES.set("Resctrl", True)
        try:
            states = StatesInformer()
            registry = HookRegistry()
            register_default_hooks(registry, node_slo=lambda: crds.NodeSLO())
            updater = ResctrlUpdater(cfg)
            rec = Reconciler(states, registry,
                             ResourceUpdateExecutor(cfg=cfg), cfg,
                             resctrl_updater=updater)
            p = PodMeta(
                uid="rp-1", name="rp-1", namespace="default",
                qos_class=QoSClass.LS, kube_qos="burstable",
                pids=(4321,),
                annotations={ext.ANNOTATION_RESCTRL: json.dumps(
                    {"l3": 50, "mb": 30})})
            states.set_pods([p])
            rec.reconcile_once()
            gdir = updater.fs.group_dir("koord-pod-rp-1")
            assert os.path.isdir(gdir)
            assert "MB:0=30" in open(os.path.join(gdir, "schemata")).read()
            assert "4321" in open(os.path.join(gdir, "tasks")).read()
            # quiet pass: unchanged state rewrites nothing
            os.unlink(os.path.join(gdir, "schemata"))
            rec.reconcile_once()
            assert not os.path.exists(os.path.join(gdir, "schemata"))
            # a group left on disk from BEFORE a restart is cleaned too
            fresh = Reconciler(states, registry,
                               ResourceUpdateExecutor(cfg=cfg), cfg,
                               resctrl_updater=ResctrlUpdater(cfg))
            os.makedirs(updater.fs.group_dir("koord-pod-ghost"),
                        exist_ok=True)
            states.set_pods([])   # pod leaves the node
            fresh.reconcile_once()
            assert not os.path.isdir(gdir)
            assert not os.path.isdir(updater.fs.group_dir("koord-pod-ghost"))
        finally:
            RUNTIMEHOOK_GATES.set("Resctrl", False)


class TestKoordletDeviceReporting:
    def test_advisor_builds_device_cr(self, cfg):
        from koordinator_tpu.koordlet import metricsadvisor as ma
        from koordinator_tpu.koordlet.metriccache import MetricCache
        from koordinator_tpu.koordlet.statesinformer import StatesInformer

        # fake one accelerator + one rdma device on the node fs
        fake_accel_device(cfg, "accel0", uuid="GPU-0", mem_total="81920",
                          mem_used="0", usage_pct="0", numa_node="0")
        ib = os.path.join(cfg.sys_root, "class", "infiniband", "mlx5_0")
        os.makedirs(ib, exist_ok=True)

        advisor = ma.MetricsAdvisor(StatesInformer(), MetricCache(), cfg)
        KOORDLET_GATES.set("Accelerators", True)
        KOORDLET_GATES.set("RDMADevices", True)
        try:
            device = advisor.build_device("n0")
        finally:
            KOORDLET_GATES.set("Accelerators", False)
            KOORDLET_GATES.set("RDMADevices", False)
        types = sorted(d.type for d in device.devices)
        assert types == ["gpu", "rdma"]
        assert device.node_name == "n0"
        # feeds the scheduler inventory bridge end to end
        from koordinator_tpu.koordlet.devices import (
            device_infos_to_inventory,
        )

        inv = device_infos_to_inventory(list(device.devices))
        assert inv["gpu"][0]["memory"] == 81920

    def test_daemon_ticks_device_report_with_dedup(self, cfg):
        from koordinator_tpu.koordlet.daemon import Daemon

        fake_accel_device(cfg, "accel0", type="xpu", uuid="XPU-0",
                          minor="0")
        # vendor JSON drop claims the SAME (type, minor): first wins
        root = os.path.join(cfg.var_run_root, "xpu-device-infos")
        os.makedirs(root, exist_ok=True)
        with open(os.path.join(root, "dev0.json"), "w") as f:
            json.dump({"uuid": "XPU-DUPE", "minor": 0}, f)

        os.makedirs(cfg.proc_root, exist_ok=True)
        with open(cfg.proc_path("stat"), "w") as f:
            f.write("cpu  0 0 0 0 0 0 0 0 0 0\n")
        with open(cfg.proc_path("meminfo"), "w") as f:
            f.write("MemTotal: 1024 kB\nMemAvailable: 512 kB\nCached: 0\n")

        from koordinator_tpu.koordlet.statesinformer import NodeInfo

        reports = []
        t = [1000.0]
        daemon = Daemon(cfg=cfg, clock=lambda: t[0],
                        device_report_fn=reports.append,
                        device_report_interval_seconds=60.0)
        KOORDLET_GATES.set("Accelerators", True)
        try:
            daemon.tick()            # node unknown yet: no anonymous report
            assert reports == []
            daemon.states.set_node(NodeInfo(name="n0", allocatable={}))
            daemon.tick()            # ...and no extra-interval penalty
            assert len(reports) == 1
            xpus = [d for d in reports[0].devices if d.type == "xpu"]
            assert [d.uuid for d in xpus] == ["XPU-0"]  # dedup: sysfs wins
            daemon.tick()                 # within the interval: no re-report
            assert len(reports) == 1
            t[0] += 61.0
            daemon.tick()
            assert len(reports) == 2
        finally:
            KOORDLET_GATES.set("Accelerators", False)


class TestDevicePluginAdapter:
    """DevicePluginAdaption gate (device_plugin_adapter.go): translate the
    repo's device-allocated payload into vendor device-plugin dialects."""

    GiB_MiB = 1024  # 1 GiB in the MiB units device tensors use

    def _alloc(self, minors=(0,), core=100, memory=None):
        memory = self.GiB_MiB if memory is None else memory
        return {"gpu": [
            {"minor": m, "resources": {"core": core, "memory": memory}}
            for m in minors
        ]}

    def test_general_adapter_bind_timestamp_and_minors(self):
        from koordinator_tpu.scheduler.device_plugin_adapter import (
            ANNOTATION_BIND_TIMESTAMP,
            ANNOTATION_GPU_MINORS,
            adapt_for_device_plugin,
        )

        res = adapt_for_device_plugin(
            self._alloc(minors=(1, 3)), clock=lambda: 12.0)
        assert res.pod_annotations[ANNOTATION_BIND_TIMESTAMP] == str(
            int(12.0 * 1e9))
        assert res.pod_annotations[ANNOTATION_GPU_MINORS] == "1,3"
        assert not res.node_annotations

    def test_huawei_npu_dialects(self):
        from koordinator_tpu.scheduler.device_plugin_adapter import (
            ANNOTATION_HUAWEI_ASCEND_310P,
            ANNOTATION_HUAWEI_NPU_CORE,
            ANNOTATION_PREDICATE_TIME,
            adapt_for_device_plugin,
        )

        res = adapt_for_device_plugin(
            self._alloc(minors=(2,)), gpu_vendor="huawei")
        assert res.pod_annotations[ANNOTATION_HUAWEI_NPU_CORE] == "2"
        assert ANNOTATION_PREDICATE_TIME in res.pod_annotations
        # vNPU template
        alloc = self._alloc(minors=(2,))
        alloc["gpu"][0]["template"] = "vir04"
        res = adapt_for_device_plugin(alloc, gpu_vendor="huawei")
        assert res.pod_annotations[ANNOTATION_HUAWEI_NPU_CORE] == "2-vir04"
        # Ascend 310P model prefixes minors
        res = adapt_for_device_plugin(
            self._alloc(minors=(0, 1)), gpu_vendor="huawei",
            gpu_model="Ascend-310P3-300I-DUO")
        assert res.pod_annotations[ANNOTATION_HUAWEI_ASCEND_310P] == \
            "Ascend310P-0,Ascend310P-1"

    def test_cambricon_profile_and_node_lock(self):
        from koordinator_tpu.scheduler.device_plugin_adapter import (
            ANNOTATION_CAMBRICON_ASSIGNED,
            ANNOTATION_CAMBRICON_LOCK,
            ANNOTATION_CAMBRICON_PROFILE,
            AdaptError,
            adapt_for_device_plugin,
        )

        res = adapt_for_device_plugin(
            self._alloc(minors=(1,), core=50, memory=2 * self.GiB_MiB),
            gpu_vendor="cambricon", clock=lambda: 100.0)
        assert res.pod_annotations[ANNOTATION_CAMBRICON_ASSIGNED] == "false"
        # 2 GiB / 256 MiB = 8 vmemory units
        assert res.pod_annotations[ANNOTATION_CAMBRICON_PROFILE] == "1_50_8"
        assert ANNOTATION_CAMBRICON_LOCK in res.node_annotations
        # multi-device share is not expressible
        with pytest.raises(AdaptError, match="multiple gpu share"):
            adapt_for_device_plugin(
                self._alloc(minors=(0, 1)), gpu_vendor="cambricon")
        # a held, fresh node lock rejects the bind
        with pytest.raises(AdaptError, match="lock"):
            adapt_for_device_plugin(
                self._alloc(minors=(1,), memory=2 * self.GiB_MiB),
                gpu_vendor="cambricon", clock=lambda: 130.0,
                node_annotations=dict(res.node_annotations))
        # ...but a stale one (> 5 min) is overwritten
        res2 = adapt_for_device_plugin(
            self._alloc(minors=(1,), memory=2 * self.GiB_MiB),
            gpu_vendor="cambricon", clock=lambda: 100.0 + 301.0,
            node_annotations=dict(res.node_annotations))
        assert ANNOTATION_CAMBRICON_LOCK in res2.node_annotations

    def test_metax_json_and_units(self):
        from koordinator_tpu.scheduler.device_plugin_adapter import (
            ANNOTATION_HAMI_LOCK,
            ANNOTATION_METAX_ALLOCATED,
            adapt_for_device_plugin,
        )

        res = adapt_for_device_plugin(
            self._alloc(minors=(0,), core=25, memory=512),
            gpu_vendor="metax")
        data = json.loads(res.pod_annotations[ANNOTATION_METAX_ALLOCATED])
        assert data == [[{"uuid": "0", "compute": 25, "vRam": 512}]]
        assert ANNOTATION_HAMI_LOCK in res.node_annotations

    def test_scheduler_bind_path_behind_gate(self):
        import numpy as np

        from koordinator_tpu.features import SCHEDULER_GATES
        from koordinator_tpu.api.resources import ResourceDim
        from koordinator_tpu.scheduler.device_manager import DeviceManager
        from koordinator_tpu.scheduler.device_plugin_adapter import (
            ANNOTATION_GPU_MINORS,
            LABEL_GPU_VENDOR,
        )
        from tests.test_scheduler import mk_scheduler, node, pod

        dm = DeviceManager()
        dm.register_node_devices("gpu", "n1", [
            {"core": 100, "memory": 4 * self.GiB_MiB, "group": 0},
        ])
        n1 = node("n1", labels={LABEL_GPU_VENDOR: "huawei"})
        n1.allocatable[ResourceDim.GPU] = 800
        n1.allocatable[ResourceDim.GPU_MEMORY] = 8 * self.GiB_MiB
        sched, binds = mk_scheduler([n1], device_manager=dm)
        p = pod("g", cpu=1_000)
        p.requests[ResourceDim.GPU] = 100
        p.requests[ResourceDim.GPU_MEMORY] = self.GiB_MiB
        old = SCHEDULER_GATES.enabled("DevicePluginAdaption")
        try:
            SCHEDULER_GATES.set("DevicePluginAdaption", True)
            sched.enqueue(p)
            res = sched.schedule_round()
            assert res.assignments == {"g": "n1"}
            dp = sched.resource_status["g"]["device-plugin"]
            assert ANNOTATION_GPU_MINORS in dp["annotations"]
            assert "huawei.com/npu-core" in dp["annotations"]
        finally:
            SCHEDULER_GATES.set("DevicePluginAdaption", old)
