"""The six binaries' parsers + assembly (reference cmd/ tree).

Each ``main_<binary>(argv)`` parses flags, applies feature gates, and
returns the assembled component graph as a small namespace object —
callers (tests, the driver, a real deployment shim) wire transports and
call ``run()`` themselves. Flags mirror the reference commands:

- koordlet            (cmd/koordlet/main.go)
- koord-scheduler     (cmd/koord-scheduler/app/server.go)
- koord-manager       (cmd/koord-manager/main.go)
- koord-descheduler   (cmd/koord-descheduler)
- koord-runtime-proxy (cmd/koord-runtime-proxy/main.go)
- koord-device-daemon (cmd/koord-device-daemon/main.go)
"""

from __future__ import annotations

import argparse
import dataclasses
import os
from typing import Any, Optional

from koordinator_tpu.cmd import (
    add_common_flags,
    add_leader_election_flags,
    apply_feature_gates,
    build_elector,
    build_self_telemetry,
)


@dataclasses.dataclass
class Assembled:
    """What a binary main() hands back: the component graph + metadata."""

    name: str
    args: argparse.Namespace
    component: Any
    elector: Optional[Any] = None
    server: Optional[Any] = None   # transport RpcServer when one was opened
    gateway: Optional[Any] = None  # HTTP/JSON gateway when one was opened
    state_sync: Optional[Any] = None  # StateSyncService (sidecar assembly)
    #: parsed component config (Scheduler/DeschedulerComponentConfig) so
    #: the embedding shell wires data-dependent plugins with file args
    component_config: Optional[Any] = None
    #: process self-telemetry sampler (selftelemetry.SelfTelemetry) —
    #: every binary registers the same leak-watch gauges under its own
    #: binary label
    telemetry: Optional[Any] = None
    #: warm-restart checkpoint writer (drills.checkpoint.CheckpointWriter)
    #: when --checkpoint-path is set; stop() writes a final cut
    checkpointer: Optional[Any] = None
    #: descheduler.migration.MigrationController, when koord-descheduler
    #: was assembled beside a scheduler: its evictions are migration jobs
    #: and whoever runs the loop calls ``migration.reconcile()``
    migration: Optional[Any] = None

    def stop(self) -> None:
        """Tear down whatever this binary opened (sockets, gateway, the
        component's own lifecycle); a leading elector releases its lease
        so a follower acquires without waiting out the duration."""
        if self.checkpointer is not None:
            self.checkpointer.stop()
        # journey-ledger fleet snapshot (ISSUE 20): every binary flushes
        # its sketch table on teardown when KOORD_JOURNEY_JSONL names a
        # path — tools/latency_report.py merges the per-process files
        # into one fleet-wide journey table (merge = bucket-wise add)
        journey_path = os.environ.get("KOORD_JOURNEY_JSONL")
        if journey_path:
            try:
                from koordinator_tpu import journey

                if journey.LEDGER.enabled:
                    journey.LEDGER.write_jsonl(journey_path)
            except Exception:
                pass
        if self.telemetry is not None:
            self.telemetry.stop()
        if self.elector is not None:
            self.elector.release()
        if self.gateway is not None:
            self.gateway.stop()
        if self.server is not None:
            self.server.stop()
        stop = getattr(self.component, "stop", None)
        if callable(stop):
            stop()


class ReconnectingSidecarClient:
    """Lazy + reconnecting RPC client for a scheduler-sidecar socket —
    ONE error policy shared by the koordlet's reporters and the
    manager's colocation loop (two hand-rolled copies had already
    diverged on RpcRemoteError handling, r5 review):

    - dials lazily on first use: no boot-order constraint between
      binaries (a missing sidecar costs the call/tick, not the process);
    - dial failures drive a circuit breaker (transport.retry): a dead
      sidecar gets backoff+jitter-paced probes — O(log) dials over an
      outage, not one per caller tick — and callers inside an open
      window fail fast with ``RpcError`` instead of re-dialing;
    - ``on_connect(client)`` runs after every (re)dial — the manager's
      ``sync.bootstrap`` rides here so its watch view resumes from
      last_rv after a sidecar restart; a failed hook closes the fresh
      client (no fd/reader-thread leak), counts as a dial failure for
      the breaker, and surfaces;
    - REMOTE errors (the peer rejecting one request over a healthy
      connection, e.g. unknown node before an upsert lands) pass
      through WITHOUT tearing the shared connection down — closing
      would kill other threads' in-flight calls and, for a watch
      client, force a needless full resync.  Exception: an ERROR with
      ``resync: true`` re-runs ``on_connect`` first (the server says
      the WATCH VIEW is stale — re-HELLO now, then let the caller's
      next tick retry its push against the fresh view);
    - transport errors drop only the client the caller saw fail (a
      racing caller may already have reconnected).
    """

    def __init__(self, addr: str, on_push=None, on_connect=None,
                 timeout: float = 10.0, breaker=None, retry_policy=None,
                 faults=None, fault_domain: str = ""):
        import threading

        from koordinator_tpu.transport.retry import CircuitBreaker

        self.addr = addr
        self.on_push = on_push
        self.on_connect = on_connect
        self.timeout = timeout
        self.faults = faults
        self.fault_domain = fault_domain
        #: pass breaker=False to disable pacing entirely (tests that
        #: want a dial per call); None builds the shared default
        self.breaker = (None if breaker is False
                        else breaker if breaker is not None
                        else CircuitBreaker(target=addr,
                                            policy=retry_policy))
        if self.faults is not None and self.breaker is not None:
            # heal seam: FaultInjector.heal() resets the breaker so the
            # healed sidecar is probed immediately, not after the
            # remaining (chaos-grown) open window
            register = getattr(self.faults, "register_breaker", None)
            if register is not None:
                register(self.breaker)
        self.resyncs = 0
        self._client = None
        self._lock = threading.Lock()

    def ensure(self):
        """Connected client, (re)dialing if needed (breaker-paced)."""
        from koordinator_tpu import metrics
        from koordinator_tpu.transport import RpcClient
        from koordinator_tpu.transport.channel import RpcError

        with self._lock:
            if self._client is None or not self._client.connected:
                if self.breaker is not None and not self.breaker.allow():
                    metrics.dial_attempts_total.inc(
                        labels={"outcome": "open"})
                    raise RpcError(
                        f"sidecar circuit open ({self.breaker.describe()})")
                self._close_locked()
                client = RpcClient(self.addr, on_push=self.on_push,
                                   timeout=self.timeout,
                                   faults=self.faults,
                                   fault_domain=self.fault_domain)
                try:
                    client.connect()
                except OSError as e:
                    if self.breaker is not None:
                        self.breaker.record_failure()
                    metrics.dial_attempts_total.inc(
                        labels={"outcome": "refused"})
                    raise RpcError(f"sidecar unreachable: {e}") from e
                if self.on_connect is not None:
                    try:
                        self.on_connect(client)
                    except BaseException:
                        # the sidecar ACCEPTED the dial but the bootstrap
                        # (HELLO/resync hook) failed: a reachable-but-
                        # unhealthy peer.  Same breaker pacing, but a
                        # distinct outcome — an operator paging on
                        # 'refused' would investigate networking/process
                        # liveness when the process is up fine
                        client.close()
                        if self.breaker is not None:
                            self.breaker.record_failure()
                        metrics.dial_attempts_total.inc(
                            labels={"outcome": "bootstrap_failed"})
                        raise
                if self.breaker is not None:
                    self.breaker.record_success()
                metrics.dial_attempts_total.inc(labels={"outcome": "ok"})
                self._client = client
            return self._client

    def call(self, *call_args, **call_kwargs):
        # the lock covers only connect/reconnect/close: RpcClient.call
        # is concurrency-safe (per-request waiter map), and holding the
        # lock across a call would serialize caller threads behind a
        # wedged sidecar for the full timeout each
        from koordinator_tpu.transport.channel import (
            RpcError,
            RpcRemoteError,
        )

        client = self.ensure()
        try:
            return client.call(*call_args, **call_kwargs)
        except RpcRemoteError as e:
            if e.resync:
                # the failed call still surfaces (its state may be gone
                # for real) and the caller's next tick runs against the
                # new view
                self.resync()
            raise
        except (RpcError, OSError):
            with self._lock:
                if self._client is client:
                    self._close_locked()
            raise

    def resync(self) -> None:
        """Server-directed resync: our watch view is stale (e.g. the
        sidecar restarted and lost a node a push named; the ERROR, or a
        run's reply, says ``resync``).  Re-HELLO on the still-healthy
        connection."""
        from koordinator_tpu import metrics

        client = self._client
        if self.on_connect is None or client is None:
            return
        self.resyncs += 1
        metrics.sync_resyncs_total.inc()
        try:
            if client.connected:
                self.on_connect(client)
        except Exception:
            pass  # resync is best effort; reconnect path remains

    # koordlint: guarded-by(self._lock)
    def _close_locked(self) -> None:
        if self._client is not None:
            self._client.close()
            self._client = None

    def close(self) -> None:
        with self._lock:
            self._close_locked()


# ---- koordlet --------------------------------------------------------------

def build_koordlet_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(prog="koordlet")
    add_common_flags(parser)
    parser.add_argument("--cgroup-root-dir", default="/sys/fs/cgroup")
    parser.add_argument("--proc-root-dir", default="/proc")
    parser.add_argument("--sys-root-dir", default="/sys")
    parser.add_argument(
        "--var-run-root-dir", default="/var/run/koordinator",
        help="where the agent keeps what outlives it: the metric cache's "
             "snapshot (restored at start, so two agents that share the "
             "directory share their history) and the prediction "
             "checkpoints")
    parser.add_argument("--cgroup-driver-systemd", action="store_true")
    parser.add_argument("--cgroup-v2", action="store_true")
    parser.add_argument("--audit-log-dir", default="")
    parser.add_argument("--collect-interval-seconds", type=float, default=1.0)
    parser.add_argument(
        "--http-port", type=int, default=None,
        help="serve the HTTP/JSON gateway (incl. /v1/podresources when "
             "the PodResourcesProxy gate is on); omit to disable")
    parser.add_argument(
        "--runtime-hook-server-addr", default="",
        help="serve the runtimehooks plugins to a runtime proxy over this "
             "address (unix path or tcp://host:port) — the nri/server.go "
             "/ proxyserver seam; empty disables")
    parser.add_argument(
        "--kubelet-addr", default="",
        help="poll this kubelet's /pods as the pod informer "
             "(states_pods.go); empty keeps the shell-fed informer")
    parser.add_argument("--kubelet-port", type=int, default=10250)
    parser.add_argument("--kubelet-scheme", default="https",
                        choices=("https", "http"))
    parser.add_argument("--kubelet-token-file", default="")
    parser.add_argument("--kubelet-ca-file", default="")
    parser.add_argument("--kubelet-insecure-skip-verify",
                        action="store_true")
    parser.add_argument("--kubelet-timeout-seconds", type=float,
                        default=5.0)
    parser.add_argument("--informer-sync-interval-seconds", type=float,
                        default=30.0)
    parser.add_argument(
        "--scheduler-sidecar-addr", default="",
        help="push this node's NodeMetric usage to a solver sidecar "
             "over STATE_PUSH node_usage frames (the states_nodemetric "
             "report loop's wire form); requires --node-name")
    parser.add_argument("--node-name", default="")
    parser.add_argument("--nodemetric-report-interval-seconds", type=float,
                        default=60.0)
    parser.add_argument(
        "--device-report-interval-seconds", type=float, default=60.0,
        help="Device-CR report cadence (the device heartbeat that also "
             "repairs server-side inventory clears)")
    return parser


def main_koordlet(argv: list[str], device_report_fn=None,
                  pod_resources_upstream_fn=None,
                  node_info_fn=None, clock=None) -> Assembled:
    """``device_report_fn(Device)`` is the deployment shell's Device-CR
    sink (apiserver client / StateSyncService.upsert_node devices=...);
    None disables the in-agent reporting tick.
    ``pod_resources_upstream_fn()`` is the kubelet pod-resources stub the
    PodResourcesProxy enriches; None serves koord allocations only.
    ``node_info_fn() -> NodeInfo`` is the shell's Node watch (the
    states_node informer); it registers as the 'node' informer the
    kubelet pods informer depends on.
    ``clock`` (default ``time.time``) is the daemon's clock: collectors
    take their rates over it and reports are dated by it."""
    import time

    from koordinator_tpu.features import KOORDLET_GATES
    from koordinator_tpu.koordlet.daemon import Daemon
    from koordinator_tpu.koordlet.system.config import SystemConfig

    args = build_koordlet_parser().parse_args(argv)
    apply_feature_gates(args.feature_gates, KOORDLET_GATES)
    cfg = SystemConfig(
        cgroup_root=args.cgroup_root_dir,
        proc_root=args.proc_root_dir,
        sys_root=args.sys_root_dir,
        var_run_root=args.var_run_root_dir,
        use_cgroup_v2=args.cgroup_v2,
        cgroup_driver_systemd=args.cgroup_driver_systemd,
    )
    daemon = Daemon(cfg=cfg, audit_dir=args.audit_log_dir or None,
                    device_report_fn=device_report_fn,
                    pod_resources_upstream_fn=pod_resources_upstream_fn,
                    informer_sync_interval_seconds=(
                        args.informer_sync_interval_seconds),
                    device_report_interval_seconds=(
                        args.device_report_interval_seconds),
                    clock=clock or time.time)
    if node_info_fn is not None:
        from koordinator_tpu.koordlet.statesinformer import CallbackInformer

        daemon.informers.register(CallbackInformer(
            "node", lambda states: states.set_node(node_info_fn())))
    if args.kubelet_addr:
        from koordinator_tpu.koordlet.kubelet_stub import KubeletStub
        from koordinator_tpu.koordlet.statesinformer import (
            CallbackInformer,
            KubeletPodsInformer,
        )

        stub = KubeletStub.connect(
            args.kubelet_addr, args.kubelet_port,
            scheme=args.kubelet_scheme,
            token_file=args.kubelet_token_file or None,
            ca_file=args.kubelet_ca_file or None,
            insecure_skip_verify=args.kubelet_insecure_skip_verify,
            timeout=args.kubelet_timeout_seconds,
        )
        if node_info_fn is None:
            # the pods informer depends on 'node'; without a shell Node
            # watch, a no-op placeholder satisfies the ordering (the
            # agent's node identity then comes from set_node callers)
            daemon.informers.register(CallbackInformer(
                "node", lambda states: None))
        daemon.informers.register(KubeletPodsInformer(stub))
        daemon.kubelet_stub = stub
    if args.scheduler_sidecar_addr:
        if not args.node_name:
            raise SystemExit(
                "--scheduler-sidecar-addr requires --node-name (the "
                "node_usage event is keyed by node)")
        import numpy as _np

        from koordinator_tpu.api.resources import resource_vector
        from koordinator_tpu.koordlet.statesinformer import (
            NodeMetricReporter,
        )
        from koordinator_tpu.transport.wire import FrameType

        sidecar = ReconnectingSidecarClient(args.scheduler_sidecar_addr)
        daemon.sidecar_client = sidecar

        def push_usage(status) -> None:
            # a degraded report (collectors silent) must not zero the
            # sidecar's view — skip and let the last usage stand
            if getattr(status, "degraded", False):
                return
            usage = resource_vector({
                "cpu": status.node_usage.cpu_milli,
                "memory": status.node_usage.memory_bytes >> 20,  # MiB
            })
            agg = None
            aggregated = status.aggregated_node_usage
            if aggregated is not None and aggregated.cpu_milli_p:
                # p95 percentile feeds the aggregated-threshold filter
                # (loadaware Aggregated args); fall back to the highest
                # recorded percentile
                pct = 0.95 if 0.95 in aggregated.cpu_milli_p else max(
                    aggregated.cpu_milli_p)
                agg = resource_vector({
                    "cpu": aggregated.cpu_milli_p[pct],
                    "memory": aggregated.memory_bytes_p.get(pct, 0) >> 20,
                })
            arrays = {"usage": _np.asarray(usage, _np.int32)}
            if agg is not None:
                arrays["agg_usage"] = _np.asarray(agg, _np.int32)
            # the colocation formula's inputs ride along (SURVEY §3.2:
            # Batch = Total - SafetyMargin - max(System, Reserved) -
            # HP.Used): system daemon usage, and the HP (Prod+Mid)
            # pod-usage sum — is_hp_band is the ONE definition shared
            # with the manager's _hp_used_cpu NodeMetric fallback
            from koordinator_tpu.api.priority import (
                PriorityClass,
                is_hp_band,
                priority_class_of,
            )

            arrays["sys_usage"] = _np.asarray(resource_vector({
                "cpu": status.system_usage.cpu_milli,
                "memory": status.system_usage.memory_bytes >> 20,
            }), _np.int32)
            hp_cpu = hp_mem = prod_cpu = prod_mem = 0
            for p in status.pods_metrics:
                if is_hp_band(p.qos_class, p.priority):
                    hp_cpu += p.usage.cpu_milli
                    hp_mem += p.usage.memory_bytes >> 20
                # prod-band usage feeds loadaware's prod-usage mode
                # (NodeSpec.prod_usage -> node_prod_usage rows)
                if priority_class_of(p.priority) is PriorityClass.PROD:
                    prod_cpu += p.usage.cpu_milli
                    prod_mem += p.usage.memory_bytes >> 20
            arrays["hp_usage"] = _np.asarray(resource_vector({
                "cpu": hp_cpu, "memory": hp_mem}), _np.int32)
            arrays["prod_usage"] = _np.asarray(resource_vector({
                "cpu": prod_cpu, "memory": prod_mem}), _np.int32)
            # request/maxUsageRequest calculate-policy inputs: the HP
            # pods' REQUEST sum and per-pod max(request, usage) sum —
            # one is_hp_band walk over the informer's pod requests.
            # Without these the manager's wire-fed NodeRecords compute
            # batch capacity as if HP pods had requested nothing and
            # silently over-advertise under those policies.
            usage_by_uid = {p.uid: p.usage for p in status.pods_metrics}
            req_cpu = req_mem = max_cpu = max_mem = 0
            for meta in daemon.states.get_all_pods():
                if not meta.is_running:
                    continue
                if not is_hp_band(meta.qos_class.name, meta.priority):
                    continue
                r_cpu = int(meta.requests.get("cpu", 0))
                r_mem = int(meta.requests.get("memory", 0)) >> 20
                req_cpu += r_cpu
                req_mem += r_mem
                used = usage_by_uid.get(meta.uid)
                u_cpu = used.cpu_milli if used is not None else 0
                u_mem = (used.memory_bytes >> 20) if used is not None else 0
                max_cpu += max(r_cpu, u_cpu)
                max_mem += max(r_mem, u_mem)
            arrays["hp_request"] = _np.asarray(resource_vector({
                "cpu": req_cpu, "memory": req_mem}), _np.int32)
            arrays["hp_max_used_req"] = _np.asarray(resource_vector({
                "cpu": max_cpu, "memory": max_mem}), _np.int32)
            sidecar.call(FrameType.STATE_PUSH,
                         {"kind": "node_usage", "name": args.node_name,
                          # the report's OWN timestamp: consumers date the
                          # usage by when the koordlet measured it, not by
                          # when the delta applied (degrade windows must
                          # survive manager restarts + snapshot replay)
                          "usage_time": float(status.update_time)},
                         arrays)

        daemon.reporters.append(NodeMetricReporter(
            daemon.states, push_usage,
            report_interval_seconds=(
                args.nodemetric_report_interval_seconds),
            clock=daemon.clock,
        ))

        if device_report_fn is None:
            # default Device-CR sink when a sidecar is wired: the
            # inventory rides node_devices frames (device daemon report
            # loop in wire form); shell-provided sinks still win
            from koordinator_tpu.koordlet.devices import (
                device_infos_to_inventory,
            )

            import threading as _threading

            device_push_inflight = _threading.Event()
            daemon.device_push_failures = 0

            def push_devices(device) -> None:
                inventory = device_infos_to_inventory(list(device.devices))
                # push EVERY interval, empty or not (heartbeat): the
                # server drops unchanged pushes without log churn
                # (update_node_devices dedups against the stored doc),
                # the periodic re-push restores inventory a server-side
                # re-upsert may have cleared, and the empty push clears
                # tensors for vanished hardware EVEN ACROSS a koordlet
                # restart (any in-process last-push cache would skip the
                # clear when the devices disappeared while we were down)
                # one in-flight push: a wedged sidecar must not pile up
                # threads (the next report interval retries)
                if device_push_inflight.is_set():
                    return
                device_push_inflight.set()

                def send() -> None:
                    try:
                        sidecar.call(
                            FrameType.STATE_PUSH,
                            {"kind": "node_devices",
                             # the daemon's registered identity, same as
                             # push_usage — a Device-CR node_name that
                             # differs is an unknown node upstream
                             "name": args.node_name,
                             "devices": inventory})
                    except Exception:  # noqa: BLE001 — COUNTED, next
                        daemon.device_push_failures += 1  # interval retries
                    finally:
                        device_push_inflight.clear()

                # off the enforcement thread, like the usage reporter
                _threading.Thread(target=send, daemon=True).start()

            daemon.device_report_fn = push_devices
    if args.http_port is not None:
        from koordinator_tpu.transport.http_gateway import HttpGateway

        daemon.gateway = HttpGateway(
            port=args.http_port,
            dispatcher=None,
            pod_resources=(daemon.pod_resources
                           if daemon.pod_resources.enabled() else None),
            auditor=(daemon.auditor
                     if KOORDLET_GATES.enabled("AuditEventsHTTPHandler")
                     else None),
        )
        daemon.gateway.start()
    if args.runtime_hook_server_addr:
        from koordinator_tpu.koordlet.runtimehooks.server import (
            RegistryHookServer,
        )
        from koordinator_tpu.runtimeproxy import Dispatcher, HookType
        from koordinator_tpu.transport import RpcServer
        from koordinator_tpu.transport.services import HookService

        hook_dispatcher = Dispatcher()
        hook_dispatcher.register(
            RegistryHookServer(daemon.hook_registry), list(HookType))
        daemon.hook_server = RpcServer(args.runtime_hook_server_addr,
                                       service="koordlet")
        HookService(hook_dispatcher).attach(daemon.hook_server)
        daemon.hook_server.start()
    return Assembled(name="koordlet", args=args, component=daemon,
                     telemetry=build_self_telemetry(args, "koordlet"))


# ---- koord-scheduler -------------------------------------------------------

def build_scheduler_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(prog="koord-scheduler")
    add_common_flags(parser)
    add_leader_election_flags(parser, default_lease="koord-scheduler")
    parser.add_argument("--node-capacity", type=int, default=1024,
                        help="initial padded node-state capacity")
    parser.add_argument("--gang-passes", type=int, default=2)
    parser.add_argument(
        "--topology-layer-labels", default="",
        help="label keys of the network-topology layers, top-down and "
             "comma-separated, between the cluster and the node (e.g. "
             "spine,block): the tree gangs with a topology requirement are "
             "gathered on is built from the nodes' labels under these keys; "
             "empty = no tree")
    parser.add_argument("--batch-solver-threshold", type=int, default=1024,
                        help="queue size at which rounds switch from the "
                             "exact greedy scan to the data-parallel "
                             "propose/accept engine")
    parser.add_argument("--enable-preemption", action="store_true")
    parser.add_argument("--sync-barrier-timeout", type=float, default=30.0,
                        help="app/sync_barrier.go wait budget")
    parser.add_argument(
        "--staleness-threshold-seconds", type=float, default=0.0,
        help="sync-feed silence (seconds) after which rounds flip into "
             "stale-state degraded mode: BE/batch-dim admission suspends "
             "and solves go full-pass until a resync re-warms the feed; "
             "0 disables the watchdog")
    parser.add_argument("--listen-socket", default="",
                        help="unix socket for the solve/state-sync RPC "
                             "services (empty = in-process only)")
    parser.add_argument(
        "--http-port", type=int, default=None,
        help="serve the HTTP/JSON gateway (solve, state push, leases, "
             "diagnosis) — the zero-client-code sidecar surface; omit "
             "to disable")
    parser.add_argument(
        "--config", default="",
        help="KubeSchedulerConfiguration YAML with per-plugin args "
             "(LoadAwareScheduling, NodeResourcesFitPlus, "
             "ScarceResourceAvoidance, Coscheduling) — the reference's "
             "versioned component config; defaults apply where unset")
    parser.add_argument(
        "--no-explain", action="store_true",
        help="disable placement explainability: the device-side "
             "reject-reason accounting (ops/explain.py), the "
             "/debug/explain/<pod> explanations, and the "
             "unschedulable_pods/filter_reject_fraction/capacity_slack "
             "rollups all go dark; Diagnose falls back to the per-pod "
             "host recompute")
    parser.add_argument(
        "--no-timeline", action="store_true",
        help="disable the critical-path observatory (timeline.py): no "
             "per-cycle segment recording, host-wait attribution, "
             "critical-path solving, or /debug/timeline bodies — the "
             "kill switch for suspected self-overhead (decisions are "
             "bit-identical either way; KOORD_TIMELINE=0 is the env "
             "equivalent)")
    parser.add_argument(
        "--no-journey", action="store_true",
        help="disable the pod-journey ledger (journey.py): no per-pod "
             "arrival/enqueue/bind latency sketches, /debug/latency "
             "answers 501, and the pod_journey_latency_seconds gauges "
             "go dark — the kill switch for suspected self-overhead "
             "(scheduling decisions and quota charges are bit-identical "
             "either way; KOORD_JOURNEY=0 is the env equivalent)")
    parser.add_argument(
        "--trace-pods", action="store_true",
        help="open a root trace span for EVERY enqueued pod (pods whose "
             "submitter propagated a trace context are always traced); "
             "spans land in the in-process ring (/debug/trace/<pod>) "
             "and any KOORD_TRACE_JSONL exporter")
    parser.add_argument(
        "--slo-sample-interval-seconds", type=float, default=0.0,
        help="background SLO burn-rate sampling cadence: every interval "
             "the registry metrics are sampled into the in-process "
             "time-series and the SLO specs' fast/slow burn windows are "
             "evaluated (breach -> alert counter + flight-recorder "
             "dump).  0 (default) = on-demand only: each GET /debug/slo "
             "request samples + evaluates; production sidecars should "
             "set e.g. 5")
    parser.add_argument(
        "--slo-latency-threshold-seconds", type=float, default=0.2,
        help="the scheduling-latency SLO's per-observation bound (the "
             "paper's p99 target: 0.2)")
    parser.add_argument(
        "--flight-ring-size", type=int, default=256,
        help="round flight-recorder ring capacity: a long soak's report "
             "joins trend verdicts to rounds, so size this to cover the "
             "report window (round_flight_overwritten_total counts the "
             "records a too-small ring silently evicts)")
    parser.add_argument(
        "--trend-window-seconds", type=float, default=1800.0,
        help="the /debug/steady trend engine's default evaluation "
             "window: slopes over the self-telemetry/queue-depth series "
             "are fitted over this much history and classified "
             "steady/drifting/leaking (?window=N overrides per request)")
    parser.add_argument(
        "--tenants", type=int, default=1,
        help="multiplex N clusters onto this scheduler's mesh "
             "(scheduler/tenancy.py): each tenant gets its own "
             "snapshot/quota/degraded state and sync binding (extra "
             "tenants listen at <listen-socket>.<tenant>), all sharing "
             "ONE compiled solver; rounds run as pipelined (or "
             "tenant-axis batched) cycles with weighted-fair admission")
    parser.add_argument(
        "--tenant-weights", default="",
        help="comma-separated weighted-fair admission weights, one per "
             "tenant (short lists pad with 1.0)")
    parser.add_argument(
        "--tenant-cycle-pod-budget", type=int, default=4096,
        help="pods admitted per multi-tenant cycle across all tenants "
             "(the weighted deficit-round-robin quantum)")
    parser.add_argument(
        "--quality-mode", choices=("off", "lp", "auto"), default="off",
        help="solve-quality mode (quality/lp_pack): off = the greedy "
             "top-k path exactly; lp = every eligible round solves "
             "with the LP-relaxation packing engine (dual-price "
             "ascent + iterative masked rounding, feasibility-checked "
             "by the greedy path's own capacity/quota kernels); auto "
             "= escalate only rounds whose result leaves min-over-dims "
             "capacity_slack_fraction above --quality-slack-threshold. "
             "Gangs with topology requirements additionally plan "
             "through the rank-aware minimal-diameter planner "
             "(quality/topo_gang) whenever the mode is not off")
    parser.add_argument(
        "--quality-slack-threshold", type=float, default=0.3,
        help="auto-mode escalation bar: when the MINIMUM "
             "capacity_slack_fraction over provisioned dims left by a "
             "round exceeds this, the next round solves on the "
             "quality path (every dimension must have headroom worth "
             "winning back)")
    parser.add_argument(
        "--forecast-mode", choices=("off", "admit", "full"), default="off",
        help="forecast plane (forecast/): off = today's solve exactly "
             "(bit-identical acceptance decisions and quota charges); "
             "admit = the forecast-headroom reserve — the predicted LS "
             "peak growth not yet visible in observed usage — charges "
             "into every round's filter/score accounting; full = "
             "admission plus the predictive-colocation and "
             "proactive-rebalance drivers where the deployment shell "
             "wires them.  Any mode other than off attaches a "
             "ForecastPlane fed from the round prelude and serves "
             "/debug/forecast")
    parser.add_argument(
        "--forecast-horizon-seconds", type=float, default=120.0,
        help="the forecast plane's base prediction horizon; stretches "
             "with the diurnal trend slope (plane.horizon_for) up to "
             "4x")
    parser.add_argument(
        "--enable-profile-endpoint", action="store_true",
        help="arm /debug/profile?seconds=N (on-demand jax.profiler "
             "capture); OFF by default — the endpoint answers 403 "
             "until an operator enables it here")
    parser.add_argument(
        "--profile-dir", default="",
        help="directory for /debug/profile trace captures (default: a "
             "fresh temp dir per capture)")
    parser.add_argument(
        "--checkpoint-path", default="",
        help="warm-restart checkpoint file (docs/robustness.md): "
             "restored on boot when present, rewritten every "
             "--checkpoint-interval-seconds and once on stop; empty "
             "disables checkpointing (behavior is bit-identical either "
             "way — the checkpoint is host state + the replay cursor, "
             "never solver state)")
    parser.add_argument(
        "--checkpoint-interval-seconds", type=float, default=30.0,
        help="cadence of the background checkpoint writer")
    return parser


def main_koord_scheduler(argv: list[str],
                         lease_store=None, preempt_fn=None) -> Assembled:
    """``preempt_fn(victim, preemptor)`` is the deployment shell's
    eviction transport; required when preemption is enabled (the flag or
    the config file), because nominating victims without evicting them
    frees accounting for pods that keep running."""
    from koordinator_tpu.features import SCHEDULER_GATES
    from koordinator_tpu.scheduler import ClusterSnapshot, Scheduler
    from koordinator_tpu.scheduler.explanation import (
        ExplanationStore,
        WorkloadAuditor,
    )

    from koordinator_tpu.scheduler.cpu_manager import CPUManager
    from koordinator_tpu.scheduler.device_manager import DeviceManager

    args = build_scheduler_parser().parse_args(argv)
    apply_feature_gates(args.feature_gates, SCHEDULER_GATES)
    # before the first jit: a restarted scheduler finds the programs its
    # predecessor compiled instead of paying every cold solve again
    from koordinator_tpu.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.no_timeline:
        from koordinator_tpu import timeline

        timeline.RECORDER.set_enabled(False)
    if args.no_journey:
        from koordinator_tpu import journey

        journey.LEDGER.set_enabled(False)
    from koordinator_tpu.cmd.component_config import (
        SchedulerComponentConfig,
        load_scheduler_config,
    )

    # always go through the component config so every default (gang
    # timeout, scoring) has exactly one home — the dataclass
    component_config = (load_scheduler_config(args.config) if args.config
                        else SchedulerComponentConfig())
    snapshot = ClusterSnapshot(capacity=args.node_capacity)
    elector = build_elector(args, lease_store)
    # precedence: an explicit CLI flag wins over the config file, which
    # wins over built-in defaults (matching the reference's flag
    # layering).  Tri-state is preserved: an explicit `enablePreemption:
    # false` in the config must reach the Scheduler as False, not
    # collapse to None (which would auto-enable when preempt_fn is
    # wired).
    enable_preemption = (True if args.enable_preemption
                         else component_config.enable_preemption)
    if enable_preemption and preempt_fn is None:
        raise SystemExit(
            "preemption enabled (flag or config) but no eviction "
            "transport wired: pass preempt_fn to main_koord_scheduler — "
            "nominating victims without evicting them double-books nodes")
    sched_kwargs = dict(
        config=component_config.scoring,
        gang_passes=args.gang_passes,
        gang_default_timeout_sec=component_config.gang_default_timeout_sec,
        batch_solver_threshold=args.batch_solver_threshold,
        enable_preemption=enable_preemption,
        preempt_fn=preempt_fn,
        elector=elector,
        staleness_threshold_sec=(args.staleness_threshold_seconds
                                 if args.staleness_threshold_seconds > 0
                                 else None),
        trace_pods=args.trace_pods,
        explain=not args.no_explain,
        flight_ring_size=args.flight_ring_size,
        quality_mode=args.quality_mode,
        quality_slack_threshold=args.quality_slack_threshold,
        forecast_mode=args.forecast_mode,
        topology_layers=[k.strip() for k in
                         args.topology_layer_labels.split(",") if k.strip()],
    )
    tenant_front = None
    if args.tenants > 1:
        # multi-tenant assembly (ISSUE 11): one TenantScheduler front
        # multiplexes N per-tenant Schedulers — each with its OWN
        # explanation store / auditor / fine-grained managers — onto one
        # shared SolverKit.  Leadership gates the WHOLE cycle at the
        # front (a standby must not decide for any tenant), so the
        # per-tenant schedulers run ungated.
        from koordinator_tpu.scheduler.tenancy import (
            TenantScheduler,
            TenantSpec,
        )

        # positions matter: an empty item (trailing/doubled comma) must
        # fail LOUDLY, not silently shift later tenants' weights; short
        # lists pad with 1.0, longer-than---tenants lists are an error
        weights = ([float(w) for w in args.tenant_weights.split(",")]
                   if args.tenant_weights.strip() else [])
        if len(weights) > args.tenants:
            raise SystemExit(
                f"--tenant-weights names {len(weights)} weights for "
                f"--tenants {args.tenants}")
        tenant_front = TenantScheduler(
            cycle_pod_budget=args.tenant_cycle_pod_budget)
        tenant_front.elector = elector
        for i in range(args.tenants):
            kw = dict(sched_kwargs)
            kw.update(elector=None,
                      explanations=ExplanationStore(),
                      auditor=WorkloadAuditor(),
                      cpu_manager=CPUManager(),
                      device_manager=DeviceManager())
            if i == 0:
                kw["snapshot"] = snapshot
            tenant_front.add_tenant(
                TenantSpec(name=f"t{i}",
                           weight=(weights[i] if i < len(weights)
                                   else 1.0),
                           node_capacity=args.node_capacity), **kw)
        scheduler = tenant_front.primary
    else:
        scheduler = Scheduler(
            snapshot,
            explanations=ExplanationStore(),
            auditor=WorkloadAuditor(),
            cpu_manager=CPUManager(),
            device_manager=DeviceManager(),
            **sched_kwargs,
        )
    # -- self-observability: SLO burn-rate engine + solver introspection
    from koordinator_tpu import journey as _journey
    from koordinator_tpu.ops.introspection import ProfilerCapture
    from koordinator_tpu.slo_monitor import (
        SloMonitor,
        default_specs,
        tenant_slo_specs,
    )
    from koordinator_tpu.trend import TrendEngine

    # self-telemetry rides the SLO sampler (every sweep — background OR
    # on-demand /debug/slo//debug/steady — refreshes RSS/fds/threads
    # first), so the scheduler needs no second sampling thread
    telemetry = build_self_telemetry(args, "koord-scheduler")
    slo_specs = default_specs(
        latency_threshold_s=args.slo_latency_threshold_seconds,
        staleness_threshold_s=(args.staleness_threshold_seconds
                               if args.staleness_threshold_seconds > 0
                               else 30.0))
    if tenant_front is not None:
        # per-tenant p99 specs slice the shared latency histogram by
        # its {tenant=...} label, so one tenant's breach pages AS that
        # tenant instead of diluting into the global p99
        slo_specs += tenant_slo_specs(
            [t.name for t in tenant_front.tenants()],
            latency_threshold_s=args.slo_latency_threshold_seconds)
    slo_monitor = SloMonitor(
        specs=slo_specs,
        sample_interval_s=(args.slo_sample_interval_seconds
                           if args.slo_sample_interval_seconds > 0 else 5.0),
        # a fast-burn breach dumps the latest round's flight record with
        # the offending SLO named — the "why" artifact next to the alert
        on_breach=lambda spec, doc: scheduler.flight_recorder.dump_now(
            f"slo:{spec.name}"),
        # the journey ledger's quantile gauges refresh in the SAME sweep
        # that evaluates the SLO windows, so burn rates compute from true
        # per-pod e2e quantiles instead of round-bucket interpolation
        pre_sample=[telemetry.sample, _journey.LEDGER.publish_gauges],
    )
    scheduler.slo_monitor = slo_monitor
    # the trend engine shares the SLO monitor's sample cache: one
    # sampling pass feeds burn rates AND the long-horizon leak watch
    scheduler.trend_engine = TrendEngine(
        slo_monitor.cache, window_s=args.trend_window_seconds)
    if tenant_front is not None:
        tenant_front.slo_monitor = slo_monitor
        tenant_front.trend_engine = scheduler.trend_engine
    if args.slo_sample_interval_seconds > 0:
        slo_monitor.start()   # stopped via Assembled.stop -> Scheduler.stop
    if args.enable_profile_endpoint:
        scheduler.profile_capture = ProfilerCapture(
            enabled=True, out_dir=args.profile_dir or None)
    if args.forecast_mode != "off":
        # the forecast plane (ISSUE 15): fed from the round prelude,
        # pinned under the solver mesh's node sharding when active, and
        # served at /debug/forecast on both surfaces.  Multi-tenant
        # assemblies attach one plane per tenant — each tenant's usage
        # history is its own signal.
        from koordinator_tpu.forecast.plane import ForecastPlane

        planes = (
            [(t.scheduler, t.scheduler.snapshot)
             for t in tenant_front.tenants()]
            if tenant_front is not None else [(scheduler, snapshot)])
        for sched, snap in planes:
            sched.attach_forecast_plane(ForecastPlane(
                snap.capacity,
                base_horizon_s=args.forecast_horizon_seconds,
                mesh=(sched.kit.mesh
                      if sched.kit.sharding_active_for(snap.capacity)
                      else None)))
    server = None
    sync_service = None
    if args.listen_socket or args.http_port is not None:
        # the SIDECAR assembly: state enters over STATE_PUSH frames or
        # POST /v1/state, lands in the sync service, and applies to the
        # scheduler synchronously through an in-process binding — the
        # same commit->binding path remote sync clients ride, minus the
        # socket loop.  Remote replicas can still HELLO the same service
        # for snapshots/deltas.
        from koordinator_tpu.transport.deltasync import (
            SchedulerBinding,
            StateSyncService,
        )

        sync_service = StateSyncService()
        sync_service.attach_binding(SchedulerBinding(scheduler))
        if tenant_front is not None:
            # per-tenant sync bindings: every EXTRA tenant gets its own
            # StateSyncService (its informer feed, its staleness clock —
            # isolation is per feed) served on its own socket below;
            # the primary tenant rides the main socket/gateway
            tenant_front.tenant_syncs = {}
            for t in tenant_front.tenants()[1:]:
                svc = StateSyncService()
                svc.attach_binding(SchedulerBinding(t.scheduler))
                tenant_front.tenant_syncs[t.name] = svc
    # the lease surface (frames + HTTP) must share the elector's store:
    # a private store would let a remote contender "acquire" a lease the
    # local elector also holds in the real one — split-brain
    shared_lease_store = (elector.store if elector is not None
                          else lease_store)
    if shared_lease_store is None:
        from koordinator_tpu.ha import InMemoryLeaseStore

        shared_lease_store = InMemoryLeaseStore()
    if args.listen_socket:
        from koordinator_tpu.ha import LeaseService
        from koordinator_tpu.transport import RpcServer
        from koordinator_tpu.transport.services import SolveService

        server = RpcServer(args.listen_socket, service="scheduler")
        # a multi-tenant assembly solves CYCLES: the solve frame drives
        # the front-end (weighted admission + pipelined/batched rounds
        # across every tenant), not one tenant's round
        SolveService(tenant_front if tenant_front is not None
                     else scheduler).attach(server)
        sync_service.attach(server)
        LeaseService(store=shared_lease_store).attach(server)
        server.start()
        if tenant_front is not None:
            for name, svc in tenant_front.tenant_syncs.items():
                extra = RpcServer(f"{args.listen_socket}.{name}",
                                  service="scheduler")
                svc.attach(extra)
                extra.start()
                tenant_front.closers.append(extra.stop)
    gateway = None
    if args.http_port is not None:
        from koordinator_tpu.transport.http_gateway import HttpGateway

        gateway = HttpGateway(port=args.http_port, scheduler=scheduler,
                              state_sync=sync_service,
                              lease_store=shared_lease_store)
        gateway.start()
    checkpointer = None
    if args.checkpoint_path:
        import os as _os

        from koordinator_tpu.drills import checkpoint as _ckpt

        if _os.path.exists(args.checkpoint_path):
            # warm restart: restore the host-side cut before any state
            # arrives, so informer replay / remote deltas land on the
            # restored generations instead of re-placing the world
            _ckpt.restore(args.checkpoint_path, scheduler)
        checkpointer = _ckpt.CheckpointWriter(
            args.checkpoint_path, scheduler,
            interval_s=args.checkpoint_interval_seconds).start()
    return Assembled(name="koord-scheduler", args=args,
                     component=(tenant_front if tenant_front is not None
                                else scheduler),
                     elector=elector, server=server,
                     gateway=gateway, state_sync=sync_service,
                     component_config=component_config,
                     telemetry=telemetry, checkpointer=checkpointer)


# ---- koord-manager ---------------------------------------------------------

def build_manager_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(prog="koord-manager")
    add_common_flags(parser)
    add_leader_election_flags(parser, default_lease="koord-manager")
    parser.add_argument("--sync-period", type=float, default=0.0)
    parser.add_argument("--config-namespace", default="koordinator-system")
    parser.add_argument("--slo-config-name", default="slo-controller-config")
    parser.add_argument(
        "--sloconfig-file", default="",
        help="bootstrap the slo-controller-config ConfigMap DATA from a "
             "YAML file (same keys: colocation-config, "
             "resource-threshold-config, ...) until the watched CM "
             "arrives; rejected loudly when invalid")
    parser.add_argument(
        "--scheduler-sidecar-addr", default="",
        help="scheduler sidecar socket: watch node state + koordlet "
             "usage reports from its sync service and push the "
             "noderesource reconcile's batch/mid allocatable back as "
             "node_allocatable events (the §3.2 colocation loop's "
             "manager leg in wire form)")
    parser.add_argument(
        "--http-port", type=int, default=None,
        help="serve the HTTP/JSON gateway (/healthz, /metrics over all "
             "component registries) — the manager's scrape surface; "
             "omit to disable")
    return parser


def main_koord_manager(argv: list[str], lease_store=None,
                       clock=None) -> Assembled:
    """``clock`` (default ``time.time``) is the report clock of an
    embedding shell that steps time itself: it dates the noderesource
    reconcile (degrade window, time-gap rule) and the watch's undated
    usage reports."""
    import time
    import types

    clock = clock or time.time

    from koordinator_tpu.features import SCHEDULER_GATES  # manager+scheduler
    from koordinator_tpu.manager.nodemetric import NodeMetricController
    from koordinator_tpu.manager.nodeslo import NodeSLOController
    from koordinator_tpu.manager.noderesource_controller import (
        NodeResourceController,
    )
    from koordinator_tpu.manager.quota_profile import QuotaProfileController
    from koordinator_tpu.manager.recommendation import (
        RecommendationController,
    )
    from koordinator_tpu.manager.node_webhook import (
        NodeMutatingWebhook,
        NodeValidatingWebhook,
    )
    from koordinator_tpu.manager.quota_webhook import QuotaTopologyValidator
    from koordinator_tpu.manager.webhook import (
        MultiQuotaTreeAffinity,
        PodMutatingWebhook,
        PodValidatingWebhook,
    )

    args = build_manager_parser().parse_args(argv)
    apply_feature_gates(args.feature_gates, SCHEDULER_GATES)
    from koordinator_tpu.manager import sloconfig

    config_data: dict[str, str] = {}
    colocation = None
    if args.sloconfig_file:
        try:
            config_data = sloconfig.load_config_file(args.sloconfig_file)
        except ValueError as e:
            raise SystemExit(str(e)) from e
        # only override the controller's enable-by-default colocation
        # config when the file actually carries that key — bootstrapping
        # an unrelated key must not silently disable colocation
        if sloconfig.KEY_COLOCATION in config_data:
            colocation = sloconfig.parse_colocation_config(config_data)
    component = types.SimpleNamespace(
        nodemetric=NodeMetricController(),
        nodeslo=NodeSLOController(config_data=config_data or None),
        noderesource=NodeResourceController(config=colocation, clock=clock),
        pod_mutating=PodMutatingWebhook(),
        pod_validating=PodValidatingWebhook(),
        node_mutating=NodeMutatingWebhook(),
        node_validating=NodeValidatingWebhook(),
        quota_validating=QuotaTopologyValidator(
            enable_update_resource_key=SCHEDULER_GATES.enabled(
                "ElasticQuotaEnableUpdateResourceKey"),
            guarantee_usage=SCHEDULER_GATES.enabled(
                "ElasticQuotaGuaranteeUsage"),
        ),
        quota_profile=QuotaProfileController(),
        recommendation=RecommendationController(),
        # gated like the reference's multi-quota-tree webhook registration
        multi_tree_affinity=(MultiQuotaTreeAffinity()
                             if SCHEDULER_GATES.enabled("MultiQuotaTree")
                             else None),
    )

    def update_sloconfig(new_data) -> list[str]:
        """The watched-CM seam: when the live slo-controller-config CM
        changes, the deployment shell calls this — NodeSLOs re-render
        and the colocation math follows, so a --sloconfig-file bootstrap
        really is only 'until the watched CM arrives'."""
        errors = sloconfig.validate_config_data(new_data)
        if errors:
            return []   # the reference keeps the last good config
        changed = component.nodeslo.update_config(new_data)
        if sloconfig.KEY_COLOCATION in new_data:
            component.noderesource.config = (
                sloconfig.parse_colocation_config(new_data))
        return changed

    component.update_sloconfig = update_sloconfig

    if args.scheduler_sidecar_addr:
        from koordinator_tpu.manager.colocation_loop import (
            ColocationLoop,
            ManagerSyncBinding,
            sidecar_push,
        )
        from koordinator_tpu.transport import StateSyncClient

        binding = ManagerSyncBinding(clock=clock)
        sync = StateSyncClient(binding)

        def bootstrap_watch(client):
            # bind_client first: a detected rv gap on THIS stream can
            # then self-heal by severing it (the next tick's ensure
            # re-dials and lands back here to re-HELLO from last_rv)
            sync.bind_client(client)
            sync.bootstrap(client)

        # lazy like the koordlet's reporters: a manager deployed before
        # the scheduler binary must not crash at assembly — the first
        # tick's ensure_fn dials (and re-bootstraps the watch from
        # last_rv after any reconnect)
        sidecar = ReconnectingSidecarClient(
            args.scheduler_sidecar_addr, on_push=sync.on_push,
            on_connect=bootstrap_watch)

        component.sync_binding = binding
        component.sync = sync
        component.sync_client = sidecar
        component.colocation_loop = ColocationLoop(
            component.noderesource, binding, sidecar_push(sidecar),
            ensure_fn=sidecar.ensure)

        def stop() -> None:
            component.colocation_loop.stop()
            sidecar.close()

        component.stop = stop

    gateway = None
    if args.http_port is not None:
        from koordinator_tpu.transport.http_gateway import HttpGateway

        gateway = HttpGateway(port=args.http_port)
        gateway.start()
    return Assembled(name="koord-manager", args=args, component=component,
                     elector=build_elector(args, lease_store),
                     gateway=gateway,
                     telemetry=build_self_telemetry(args, "koord-manager"))


# ---- koord-descheduler -----------------------------------------------------

#: upstream ports that can't assemble from flags alone (they need a nodes_fn)
_NEEDS_NODES_FN = {
    "RemovePodsViolatingNodeAffinity",
    "RemovePodsViolatingNodeTaints",
    "RemovePodsViolatingTopologySpreadConstraint",
    "HighNodeUtilization",
}


def _flag_selectable_descheduler_plugins() -> list[str]:
    """Lower-cased names accepted by --deschedule-plugins, derived from the
    upstream.PLUGINS registry so the help text can never drift from what the
    selector below actually accepts (unknown names are a hard SystemExit)."""
    from koordinator_tpu.descheduler import upstream

    return [name.lower() for name in upstream.PLUGINS
            if name not in _NEEDS_NODES_FN]


def build_descheduler_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(prog="koord-descheduler")
    add_common_flags(parser)
    add_leader_election_flags(parser, default_lease="koord-descheduler")
    parser.add_argument("--descheduling-interval-seconds", type=float,
                        default=120.0)
    parser.add_argument("--max-evictions-per-round", type=int, default=None,
                        help="0 = unlimited; omit to defer to the config")
    parser.add_argument("--evict-system-critical", action="store_true")
    parser.add_argument("--evict-local-storage-pods", action="store_true")
    parser.add_argument("--priority-threshold", type=int, default=None)
    parser.add_argument(
        "--deschedule-plugins", default="",
        help="comma list of DESCHEDULE plugins for the default profile: "
             + ",".join(sorted(_flag_selectable_descheduler_plugins())))
    parser.add_argument("--pod-lifetime-max-seconds", type=float,
                        default=None)
    parser.add_argument("--pod-restart-threshold", type=int, default=None)
    parser.add_argument(
        "--config", default="",
        help="DeschedulerConfiguration YAML with profile plugin "
             "enablement + per-plugin args (LowNodeLoad thresholds, "
             "MigrationController limits, DefaultEvictor, ...) — the "
             "reference's versioned component config; explicit CLI "
             "flags override")
    return parser


def main_koord_descheduler(argv: list[str], pods_fn=None,
                           lease_store=None, scheduler=None) -> Assembled:
    """``scheduler``: the assembled koord-scheduler (:class:`Assembled`)
    this descheduler runs beside, in one process over one device-resident
    state.  With one, LowNodeLoad can be asked for by flag or config: it
    reads that scheduler's state, its evictions become PodMigrationJobs,
    and the migration controller (``Assembled.migration``) reserves the
    replacements' capacity through that scheduler, one round a reconcile,
    before it evicts through it."""
    from koordinator_tpu.descheduler.framework import (
        Descheduler,
        Evictor,
        EvictorFilter,
        Profile,
    )

    from koordinator_tpu.cmd.descheduler_config import (
        DeschedulerComponentConfig,
        load_descheduler_config,
    )

    args = build_descheduler_parser().parse_args(argv)
    component = (load_descheduler_config(args.config) if args.config
                 else DeschedulerComponentConfig())
    # precedence: explicit CLI flag > config file > built-in default
    # (booleans or-combine; None-defaulted flags defer to the config)
    priority_threshold = (args.priority_threshold
                          if args.priority_threshold is not None
                          else component.priority_threshold)
    max_evictions = (args.max_evictions_per_round
                     if args.max_evictions_per_round is not None
                     else component.max_evictions_per_round)
    lifetime_max = (args.pod_lifetime_max_seconds
                    if args.pod_lifetime_max_seconds is not None
                    else component.pod_lifetime_max_seconds
                    or 7 * 24 * 3600.0)
    restart_threshold = (args.pod_restart_threshold
                         if args.pod_restart_threshold is not None
                         else component.pod_restart_threshold or 100)
    migration = None
    evictor = Evictor()
    if scheduler is not None:
        from koordinator_tpu.descheduler import plugins as dplugins
        from koordinator_tpu.descheduler.migration import MigrationController

        sched = scheduler.component
        migration = MigrationController(
            limits=component.migration_limits,
            reserve_many=dplugins.scheduler_reserve_many(sched),
            evict_fn=dplugins.scheduler_migration_evict_fn(sched),
            controller_finder=dplugins.BoundOwnersFinder(sched))
        evictor = Evictor(evict_fn=dplugins.migration_evict_fn(migration))
        if pods_fn is None:
            pods_fn = dplugins.bound_pods_fn(sched)
    evictor_filter = EvictorFilter(
        evict_system_critical=(args.evict_system_critical
                               or component.evict_system_critical),
        evict_local_storage=(args.evict_local_storage_pods
                             or component.evict_local_storage_pods),
        priority_threshold=priority_threshold,
        migrating_fn=migration.migrating_pods if migration else None,
    )
    # upstream-port plugins selectable by name, derived from the single
    # upstream.PLUGINS registry (the reference's profile pluginConfig).
    # Plugins needing a nodes_fn can't assemble from flags alone and are
    # excluded; per-plugin required kwargs come from the flag table.
    from koordinator_tpu.descheduler import upstream

    flag_kwargs = {
        "PodLifeTime": lambda: {"max_seconds": lifetime_max},
        "RemovePodsHavingTooManyRestarts": lambda: {
            "pod_restart_threshold": restart_threshold},
    }
    available = {
        name.lower(): (cls, flag_kwargs.get(name, dict))
        for name, cls in upstream.PLUGINS.items()
        if name not in _NEEDS_NODES_FN
    }
    deschedule_plugins = []
    balance_plugins = []
    #: args-in-the-file, data-callables-from-the-shell plugins: the
    #: loader validates their args (exposed via Assembled.component_
    #: config), but only the embedding shell can construct them
    shell_wired = {"lownodeload", "fragmentationaware"} | {
        n.lower() for n in _NEEDS_NODES_FN}
    requested: list[tuple[str, bool]] = []   # (name, from_config)
    seen: set[str] = set()
    for raw, from_config in (
            [(r.strip(), False)
             for r in args.deschedule_plugins.split(",") if r.strip()]
            + [(n, True) for n in (component.deschedule_enabled
                                   + component.balance_enabled)]):
        if raw.lower() in seen:
            continue   # duplicates must not instantiate a plugin twice
        seen.add(raw.lower())
        requested.append((raw, from_config))
    for raw, from_config in requested:
        name = raw.lower()
        if name == "lownodeload" and scheduler is not None:
            balance_plugins.append(dplugins.LowNodeLoadPlugin(
                scheduler=sched, args=component.lownodeload))
            continue
        if name in shell_wired:
            if from_config:
                continue   # shell reads asm.component_config and wires it
            raise SystemExit(
                f"plugin {raw} needs data callables the CLI cannot "
                f"provide; the embedding shell must wire it (its config "
                f"args load via --config)")
        entry = available.get(name)
        if entry is None:
            raise SystemExit(f"unknown deschedule plugin: {raw}")
        cls, kwargs = entry
        plugin = cls(**kwargs())
        # upstream ports come in both kinds; route by interface
        if hasattr(plugin, "deschedule"):
            deschedule_plugins.append(plugin)
        else:
            balance_plugins.append(plugin)
    profile = Profile(
        name="default",
        deschedule_plugins=deschedule_plugins,
        balance_plugins=balance_plugins,
        evictor_filter=evictor_filter,
        evictor=evictor,
        max_evictions_per_round=max_evictions,
    )
    elector = build_elector(args, lease_store)
    descheduler = Descheduler(
        [profile], pods_fn=pods_fn or (lambda: []),
        interval_seconds=args.descheduling_interval_seconds,
        elector=elector,
    )
    return Assembled(name="koord-descheduler", args=args,
                     component=descheduler, elector=elector,
                     component_config=component, migration=migration,
                     telemetry=build_self_telemetry(
                         args, "koord-descheduler"))


# ---- koord-runtime-proxy ---------------------------------------------------

def build_runtime_proxy_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(prog="koord-runtime-proxy")
    add_common_flags(parser)
    parser.add_argument("--remote-runtime-service-endpoint",
                        default="/var/run/containerd/containerd.sock")
    parser.add_argument("--koord-runtime-proxy-endpoint",
                        default="/var/run/koord-runtimeproxy/runtimeproxy.sock")
    parser.add_argument("--hook-server-socket", default="",
                        help="serve the hook dispatch over this unix socket")
    return parser


def main_koord_runtime_proxy(argv: list[str],
                             backend: dict | None = None) -> Assembled:
    from koordinator_tpu.runtimeproxy import CRIProxy, Dispatcher, FailoverStore

    args = build_runtime_proxy_parser().parse_args(argv)
    dispatcher = Dispatcher()
    store = FailoverStore()
    proxy = CRIProxy(dispatcher, store, backend or {})
    server = None
    if args.hook_server_socket:
        from koordinator_tpu.transport import RpcServer
        from koordinator_tpu.transport.services import HookService

        server = RpcServer(args.hook_server_socket,
                           service="runtime-proxy")
        HookService(dispatcher).attach(server)
        server.start()
    return Assembled(name="koord-runtime-proxy", args=args, component=proxy,
                     server=server,
                     telemetry=build_self_telemetry(
                         args, "koord-runtime-proxy"))


# ---- koord-device-daemon ---------------------------------------------------

def build_device_daemon_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(prog="koord-device-daemon")
    add_common_flags(parser)
    parser.add_argument("--node-name", required=True)
    parser.add_argument("--sys-root-dir", default="/sys")
    parser.add_argument("--report-interval-seconds", type=float, default=30.0)
    return parser


def main_koord_device_daemon(argv: list[str]) -> Assembled:
    from koordinator_tpu.device_daemon import DeviceDaemon

    args = build_device_daemon_parser().parse_args(argv)
    daemon = DeviceDaemon(node_name=args.node_name,
                          sys_root=args.sys_root_dir)
    return Assembled(name="koord-device-daemon", args=args, component=daemon,
                     telemetry=build_self_telemetry(
                         args, "koord-device-daemon"))


MAINS = {
    "koordlet": main_koordlet,
    "koord-scheduler": main_koord_scheduler,
    "koord-manager": main_koord_manager,
    "koord-descheduler": main_koord_descheduler,
    "koord-runtime-proxy": main_koord_runtime_proxy,
    "koord-device-daemon": main_koord_device_daemon,
}
