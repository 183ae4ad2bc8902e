"""Per-binary CLI layer (koordinator_tpu/cmd/) vs the reference's cmd/
flag surface: feature gates, leader-election flags, component wiring."""

import numpy as np
import pytest

from koordinator_tpu.cmd.binaries import (
    MAINS,
    main_koord_descheduler,
    main_koord_manager,
    main_koord_runtime_proxy,
    main_koord_scheduler,
    main_koordlet,
)
from koordinator_tpu.features import KOORDLET_GATES, SCHEDULER_GATES
from koordinator_tpu.ha import InMemoryLeaseStore


def test_all_six_binaries_registered():
    assert sorted(MAINS) == [
        "koord-descheduler", "koord-device-daemon", "koord-manager",
        "koord-runtime-proxy", "koord-scheduler", "koordlet",
    ]


def test_koordlet_flags_and_gates(tmp_path):
    before = KOORDLET_GATES.enabled("CPICollector")
    before_audit = KOORDLET_GATES.enabled("AuditEvents")
    try:
        out = main_koordlet([
            "--cgroup-root-dir", str(tmp_path / "cg"),
            "--proc-root-dir", str(tmp_path / "proc"),
            # AuditEvents defaults FALSE (koordlet_features.go:215):
            # --audit-log-dir alone must not construct an auditor
            "--feature-gates", "CPICollector=true,AuditEvents=true",
            "--audit-log-dir", str(tmp_path / "audit"),
        ])
        assert out.name == "koordlet"
        assert out.component.cfg.cgroup_root == str(tmp_path / "cg")
        assert out.component.auditor is not None
        assert KOORDLET_GATES.enabled("CPICollector") is True
    finally:
        KOORDLET_GATES.set("CPICollector", before)
        KOORDLET_GATES.set("AuditEvents", before_audit)


def test_two_koordlets_with_their_own_var_run_share_no_metric_history(
        tmp_path):
    """The metric cache is restored at start from --var-run-root-dir: a
    second agent on the default directory would take the first one's
    samples for its own (what made test_colocation_loop_binary_to_binary
    read another test's CPU usage); given its own, it starts empty."""
    from koordinator_tpu.koordlet import metriccache as mc

    def boot(var_run):
        return main_koordlet([
            "--cgroup-root-dir", str(tmp_path / "cg"),
            "--proc-root-dir", str(tmp_path / "proc"),
            "--var-run-root-dir", str(tmp_path / var_run)])

    first = boot("a")
    assert first.component.cfg.var_run_root == str(tmp_path / "a")
    first.component.metric_cache.append(mc.NODE_CPU_USAGE, 25.0)
    first.component.stop()       # snapshots under its own directory
    assert (tmp_path / "a" / "metriccache.npz").exists()
    heir, stranger = boot("a"), boot("b")
    try:
        assert heir.component.metric_cache.query(
            mc.NODE_CPU_USAGE, None, 0.0, float("inf")).avg() == 25.0
        assert stranger.component.metric_cache.query(
            mc.NODE_CPU_USAGE, None, 0.0, float("inf")).count == 0
    finally:
        heir.component.stop()
        stranger.component.stop()


def test_koordlet_serves_runtime_hooks(tmp_path):
    from koordinator_tpu.api import extension as ext
    from koordinator_tpu.runtimeproxy import HookRequest, HookType
    from koordinator_tpu.transport import RpcClient
    from koordinator_tpu.transport.services import hook_remote

    asm = main_koordlet([
        "--cgroup-root-dir", str(tmp_path / "cg"),
        "--proc-root-dir", str(tmp_path / "proc"),
        "--runtime-hook-server-addr", str(tmp_path / "hooks.sock"),
    ])
    try:
        client = RpcClient(asm.component.hook_server.path)
        client.connect()
        try:
            res = hook_remote(client, HookType.PRE_RUN_POD_SANDBOX,
                              HookRequest(
                                  pod_meta={"uid": "u1", "name": "p1"},
                                  labels={ext.LABEL_POD_QOS: "BE"}))
            # GroupIdentity (default-on) answered from the daemon's
            # registry: BE bvt from the default NodeSLO
            assert res["resources"]["cpu.bvt_warp_ns"] == "-1"
        finally:
            client.close()
    finally:
        asm.component.stop()   # daemon lifecycle stops the hook server too


def test_scheduler_assembly_with_lease_and_socket(tmp_path):
    store = InMemoryLeaseStore()
    out = main_koord_scheduler([
        "--node-capacity", "32",
        "--gang-passes", "3",
        "--identity", "sched-a",
        "--listen-socket", str(tmp_path / "sched.sock"),
    ], lease_store=store)
    try:
        sched = out.component
        assert sched.snapshot.capacity == 32
        assert sched.gang_passes == 3
        assert sched.explanations is not None and sched.auditor is not None
        assert out.elector is not None
        assert out.elector.identity == "sched-a"
        assert out.elector.lease_name == "koordinator-system/koord-scheduler"
        assert out.elector.tick() is True
        # the solve service answers over the socket
        from koordinator_tpu.transport import RpcClient
        from koordinator_tpu.transport.services import solve_remote

        client = RpcClient(out.server.path)
        client.connect()
        try:
            result = solve_remote(client)
            assert result["assignments"] == {} and result["round_pods"] == 0
        finally:
            client.close()
    finally:
        if out.server is not None:
            out.server.stop()


def test_scheduler_leader_election_disable():
    out = main_koord_scheduler(["--disable-leader-election"])
    assert out.elector is None


def test_manager_assembly_and_gates():
    before = SCHEDULER_GATES.enabled("MultiQuotaTree")
    try:
        out = main_koord_manager(
            ["--feature-gates", "MultiQuotaTree=true", "--identity", "m0"])
        assert SCHEDULER_GATES.enabled("MultiQuotaTree") is True
        assert out.component.nodemetric is not None
        assert out.component.noderesource is not None
        assert out.component.pod_mutating is not None
        assert out.elector.lease_name == "koordinator-system/koord-manager"
        # the full controller set assembles (quota profiles + VPA-ish
        # recommendation ride along with the SLO controllers)
        assert out.component.quota_profile is not None
        assert out.component.recommendation is not None
        # multi-tree affinity is gated (reference gates this webhook)
        assert out.component.multi_tree_affinity is not None
    finally:
        SCHEDULER_GATES.set("MultiQuotaTree", before)


def test_descheduler_assembly_gated_on_leadership():
    store = InMemoryLeaseStore()
    out_a = main_koord_descheduler(
        ["--descheduling-interval-seconds", "0", "--identity", "a"],
        lease_store=store)
    out_b = main_koord_descheduler(
        ["--descheduling-interval-seconds", "0", "--identity", "b"],
        lease_store=store)
    assert out_a.component.tick() == {"default": 0}
    assert out_b.component.tick() is None       # follower replica


def test_descheduler_evictor_flags():
    out = main_koord_descheduler([
        "--priority-threshold", "8000",
        "--evict-local-storage-pods",
        "--max-evictions-per-round", "5",
    ])
    profile = out.component.profiles[0]
    assert profile.evictor_filter.priority_threshold == 8000
    assert profile.evictor_filter.evict_local_storage is True
    assert profile.max_evictions_per_round == 5


def test_runtime_proxy_with_hook_socket(tmp_path):
    from koordinator_tpu.runtimeproxy import HookRequest, HookResponse, HookType
    from koordinator_tpu.transport import RpcClient
    from koordinator_tpu.transport.services import hook_remote

    out = main_koord_runtime_proxy(
        ["--hook-server-socket", str(tmp_path / "hooks.sock")])
    try:
        class Hooker:
            def handle(self, hook, request):
                return HookResponse(annotations={"seen": "1"})

        out.component.dispatcher.register(
            Hooker(), [HookType.PRE_CREATE_CONTAINER])
        client = RpcClient(out.server.path)
        client.connect()
        try:
            res = hook_remote(client, HookType.PRE_CREATE_CONTAINER,
                              HookRequest())
            assert res["annotations"] == {"seen": "1"}
        finally:
            client.close()
    finally:
        out.server.stop()


def test_device_daemon_requires_node_name():
    with pytest.raises(SystemExit):
        MAINS["koord-device-daemon"]([])
    out = MAINS["koord-device-daemon"](["--node-name", "n1"])
    assert out.component.node_name == "n1"


def test_descheduler_assembles_upstream_plugins():
    from koordinator_tpu.cmd.binaries import main_koord_descheduler
    from koordinator_tpu.descheduler.framework import PodInfo

    pods = [PodInfo(uid="old", name="old", namespace="d",
                node="n1", phase="Failed")]
    out = main_koord_descheduler([
        "--deschedule-plugins", "removefailedpods, podlifetime ,removeduplicates",
        "--disable-leader-election",
    ], pods_fn=lambda: pods)
    profile = out.component.profiles[0]
    assert len(profile.deschedule_plugins) == 2
    assert len(profile.balance_plugins) == 1
    counts = out.component.run_once()
    assert counts["default"] >= 1        # the failed pod was descheduled

    import pytest

    with pytest.raises(SystemExit):
        main_koord_descheduler(
            ["--deschedule-plugins", "nope", "--disable-leader-election"])


def test_koordlet_http_gateway_serves_podresources(tmp_path):
    import json as _json
    import urllib.request

    old = KOORDLET_GATES.enabled("PodResourcesProxy")
    KOORDLET_GATES.set("PodResourcesProxy", True)
    try:
        asm = main_koordlet([
            "--cgroup-root-dir", str(tmp_path / "cg"),
            "--proc-root-dir", str(tmp_path / "proc"),
            "--sys-root-dir", str(tmp_path / "sys"),
            "--http-port", "0",
        ])
        gw = asm.component.gateway
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{gw.port}/v1/podresources",
                    timeout=10) as resp:
                doc = _json.loads(resp.read().decode())
            assert doc == {"pod_resources": []}
        finally:
            # daemon lifecycle owns the gateway
            asm.component.stop()
        assert asm.component.gateway is None
    finally:
        KOORDLET_GATES.set("PodResourcesProxy", old)


def test_koordlet_pod_resources_upstream_seam(tmp_path):
    import json as _json
    import urllib.request

    old = KOORDLET_GATES.enabled("PodResourcesProxy")
    KOORDLET_GATES.set("PodResourcesProxy", True)
    try:
        upstream = {"pod_resources": [{
            "name": "k", "namespace": "d",
            "containers": [{"name": "c", "devices": [
                {"resource_name": "cpu", "device_ids": ["0-3"]}]}]}]}
        asm = main_koordlet([
            "--cgroup-root-dir", str(tmp_path / "cg"),
            "--proc-root-dir", str(tmp_path / "proc"),
            "--sys-root-dir", str(tmp_path / "sys"),
            "--http-port", "0",
        ], pod_resources_upstream_fn=lambda: upstream)
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{asm.component.gateway.port}"
                    f"/v1/podresources", timeout=10) as resp:
                doc = _json.loads(resp.read().decode())
            # kubelet's own listing flows through the assembled binary
            assert doc["pod_resources"][0]["containers"][0]["devices"] == [
                {"resource_name": "cpu", "device_ids": ["0-3"]}]
        finally:
            asm.component.stop()
    finally:
        KOORDLET_GATES.set("PodResourcesProxy", old)


def test_scheduler_binary_is_a_full_sidecar(tmp_path):
    """koord-scheduler --listen-socket + --http-port: state enters over
    STATE_PUSH frames or POST /v1/state, applies to the scheduler
    SYNCHRONOUSLY through the in-process binding, and the very next
    solve sees it — no eventual-consistency window."""
    import json
    import urllib.request

    import numpy as np

    from koordinator_tpu.api.resources import NUM_RESOURCE_DIMS
    from koordinator_tpu.transport import RpcClient
    from koordinator_tpu.transport.services import solve_remote
    from koordinator_tpu.transport.wire import FrameType

    asm = main_koord_scheduler([
        "--node-capacity", "16",
        "--listen-socket", str(tmp_path / "sidecar.sock"),
        "--http-port", "0",
    ])
    r = NUM_RESOURCE_DIMS
    try:
        # framed path: push a node, then solve over the same socket
        client = RpcClient(asm.server.path)
        client.connect()
        try:
            _, doc, _ = client.call(
                FrameType.STATE_PUSH,
                {"kind": "node_upsert", "name": "wire-node"},
                {"allocatable": np.asarray(
                    [8_000, 16_384] + [0] * (r - 2), np.int32)})
            assert doc["rv"] == 1

            # HTTP path: push a pod with curl-equivalent plumbing
            body = json.dumps({
                "kind": "pod_add", "name": "http-pod",
                "requests": [1_000, 1_024] + [0] * (r - 2),
            }).encode()
            req = urllib.request.Request(
                f"http://127.0.0.1:{asm.gateway.port}/v1/state",
                data=body, headers={"Content-Type": "application/json"},
                method="POST")
            with urllib.request.urlopen(req, timeout=10) as resp:
                assert json.loads(resp.read())["rv"] == 2

            # the binding applied both synchronously: first solve wins
            result = solve_remote(client)
            assert result["assignments"] == {"http-pod": "wire-node"}
        finally:
            client.close()
    finally:
        asm.stop()


def test_stop_releases_leadership_for_fast_failover():
    store = InMemoryLeaseStore()
    a = main_koord_scheduler(["--identity", "a"], lease_store=store)
    b = main_koord_scheduler(["--identity", "b"], lease_store=store)
    assert a.elector.tick() is True
    assert b.elector.tick() is False
    a.stop()   # clean shutdown releases the lease (ReleaseOnCancel)
    assert b.elector.tick() is True, "follower should acquire immediately"


def test_manager_sloconfig_bootstrap_file(tmp_path):
    import textwrap

    path = tmp_path / "slo.yaml"
    path.write_text(textwrap.dedent("""
        colocation-config:
          enable: true
          cpuReclaimThresholdPercent: 55
        resource-threshold-config:
          enable: true
          cpuSuppressThresholdPercent: 60
    """))
    out = main_koord_manager(["--sloconfig-file", str(path),
                              "--disable-leader-election"])
    assert out.component.noderesource.config.enable is True
    assert out.component.noderesource.config \
              .cpu_reclaim_threshold_percent == 55
    # the NodeSLO controller renders the bootstrapped strategy
    out.component.nodeslo.upsert_node("n1", {})
    slo = out.component.nodeslo.get("n1")
    assert slo.resource_used_threshold_with_be \
              .cpu_suppress_threshold_percent == 60


def test_manager_sloconfig_bootstrap_rejects_invalid(tmp_path):
    path = tmp_path / "slo.yaml"
    path.write_text("colocation-config:\n  cpuReclaimThresholdPercent: 300\n")
    with pytest.raises(SystemExit, match="invalid slo config"):
        main_koord_manager(["--sloconfig-file", str(path),
                            "--disable-leader-election"])


def test_manager_watched_cm_supersedes_bootstrap(tmp_path):
    import json
    import textwrap

    path = tmp_path / "slo.yaml"
    path.write_text(textwrap.dedent("""
        colocation-config:
          enable: true
          cpuReclaimThresholdPercent: 55
    """))
    out = main_koord_manager(["--sloconfig-file", str(path),
                              "--disable-leader-election"])
    assert out.component.noderesource.config \
              .cpu_reclaim_threshold_percent == 55
    # live CM update: colocation math follows, bad updates keep last good
    out.component.update_sloconfig({"colocation-config": json.dumps(
        {"enable": True, "cpuReclaimThresholdPercent": 70})})
    assert out.component.noderesource.config \
              .cpu_reclaim_threshold_percent == 70
    out.component.update_sloconfig({"colocation-config": json.dumps(
        {"cpuReclaimThresholdPercent": 300})})
    assert out.component.noderesource.config \
              .cpu_reclaim_threshold_percent == 70


def test_manager_bootstrap_without_colocation_keeps_enable_default(tmp_path):
    path = tmp_path / "slo.yaml"
    path.write_text("resource-threshold-config:\n  enable: true\n")
    out = main_koord_manager(["--sloconfig-file", str(path),
                              "--disable-leader-election"])
    assert out.component.noderesource.config.enable is True


def test_koordlet_polls_a_kubelet(tmp_path):
    """--kubelet-addr: the agent's pod informer pulls from a live kubelet
    endpoint on the daemon tick cadence (states_pods.go), with informer
    errors isolated rather than failing the tick."""
    import http.server
    import json
    import threading

    pod_list = {"items": [{
        "metadata": {"uid": "kub-1", "name": "from-kubelet",
                     "namespace": "default",
                     "labels": {"koordinator.sh/qosClass": "BE"}},
        "spec": {"containers": [{"resources": {
            "requests": {"cpu": "250m", "memory": "256Mi"}}}]},
        "status": {"phase": "Running", "qosClass": "BestEffort"},
    }]}

    class Handler(http.server.BaseHTTPRequestHandler):
        def log_message(self, fmt, *args):
            pass

        def do_GET(self):
            body = json.dumps(pod_list).encode()
            self.send_response(200)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

    server = http.server.HTTPServer(("127.0.0.1", 0), Handler)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    try:
        asm = main_koordlet([
            "--cgroup-root-dir", str(tmp_path / "cg"),
            "--proc-root-dir", str(tmp_path / "proc"),
            "--kubelet-addr", "127.0.0.1",
            "--kubelet-port", str(server.server_address[1]),
            "--kubelet-scheme", "http",
        ])
        import time as _time

        def tick_and_settle():
            # informer rounds run off the enforcement loop on their own
            # thread; wait for the in-flight round to land
            asm.component.tick()
            deadline = _time.monotonic() + 15
            while (asm.component._informer_inflight.is_set()
                   and _time.monotonic() < deadline):
                _time.sleep(0.02)
            assert not asm.component._informer_inflight.is_set()

        try:
            tick_and_settle()
            pods = asm.component.states.get_all_pods()
            assert [p.uid for p in pods] == ["kub-1"]
            assert pods[0].requests == {"cpu": 250, "memory": 256 << 20}
            assert not asm.component.informers.sync_errors

            # kubelet goes away: the tick keeps working, the error is
            # recorded, the last-good pods stay, and a fully-failed
            # round does not stamp the cadence (it will retry)
            server.shutdown()
            server.server_close()
            asm.component._last_informer_sync = float("-inf")
            tick_and_settle()
            assert "pods" in asm.component.informers.sync_errors
            assert [p.uid for p in asm.component.states.get_all_pods()] \
                == ["kub-1"]
            assert asm.component._last_informer_sync == float("-inf")
        finally:
            asm.component.stop()
    finally:
        try:
            server.shutdown()
            server.server_close()
        except Exception:
            pass
