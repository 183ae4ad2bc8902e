#!/usr/bin/env python
"""Churn load generator: a deterministic, seeded, trace-driven arrival
process for the steady-state observatory (ISSUE 9 / ROADMAP item 3).

Everything before this proved the control plane round-at-a-time;
production scale is a CONTINUOUS arrival process.  This module turns a
seed into a reproducible churn trace — Poisson pod arrivals with
diurnal rate modulation, exponential pod lifetimes, gang bursts,
quota-tree churn, node flaps — and replays it against a real scheduler
sidecar + manager + koordlet-style feeder over real sockets, reusing
the chaos soak's socket scaffolding and replay-seed discipline
(tests/test_chaos.py): the SAME seed always produces the SAME trace,
so a failing soak replays exactly.

Trace format (JSONL, one event per line, ascending virtual time)::

    {"t": 12.375, "kind": "pod_add",  "name": "p-42", "cpu": 1000,
     "memory": 1024, "qos": 0, "priority": 1000, "gang": null,
     "quota": "team-a"}
    {"t": 13.000, "kind": "pod_del",  "name": "p-17"}
    {"t": 30.125, "kind": "gang_burst", "gang": "g-3", "size": 8, ...}
    {"t": 45.500, "kind": "node_down", "name": "n-210"}
    {"t": 75.500, "kind": "node_up",   "name": "n-210"}
    {"t": 90.250, "kind": "quota_update", "quota": "team-b",
     "scale": 0.5}

``t`` is VIRTUAL seconds from soak start; the harness replays at
``time_scale``x wall compression (a 30-minute trace drives a 3-minute
wall soak at time_scale=10 without changing the event sequence).

Arrival shapes follow "A Predictive Autoscaler for Elastic Batch Jobs"
(PAPERS.md): elastic-batch pods arrive in a thinned inhomogeneous
Poisson process whose rate swings sinusoidally (the diurnal curve),
punctuated by gang bursts (tightly-coupled jobs arrive all at once)
and served with exponential lifetimes.

No JAX at module scope (marker-audit): the harness imports the
scheduler stack inside methods, so tier-1 smoke tests import this
module for trace math without paying a backend init.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import random
import sys
import threading
import time
from typing import Iterable, Optional

POD_ADD = "pod_add"
POD_DEL = "pod_del"
GANG_BURST = "gang_burst"
NODE_DOWN = "node_down"
NODE_UP = "node_up"
QUOTA_UPDATE = "quota_update"

EVENT_KINDS = (POD_ADD, POD_DEL, GANG_BURST, NODE_DOWN, NODE_UP,
               QUOTA_UPDATE)


@dataclasses.dataclass(frozen=True)
class Event:
    """One trace event (JSON-able; ``payload`` carries kind-specific
    fields)."""

    t: float
    kind: str
    name: str = ""
    payload: dict = dataclasses.field(default_factory=dict)

    def to_doc(self) -> dict:
        return {"t": self.t, "kind": self.kind, "name": self.name,
                **self.payload}

    @classmethod
    def from_doc(cls, doc: dict) -> "Event":
        doc = dict(doc)
        return cls(t=float(doc.pop("t")), kind=str(doc.pop("kind")),
                   name=str(doc.pop("name", "")), payload=doc)


@dataclasses.dataclass(frozen=True)
class LoadGenConfig:
    """One soak's knobs — everything the seed expands from."""

    seed: int = 0
    duration_s: float = 1800.0      # virtual seconds of churn
    nodes: int = 10_000
    node_cpu_milli: int = 16_000
    node_memory_mib: int = 65_536
    #: midline pod arrival rate (pods per virtual second)
    arrival_rate: float = 8.0
    #: diurnal modulation: rate(t) = arrival_rate * (1 + amp*sin(2πt/T))
    diurnal_amplitude: float = 0.5
    diurnal_period_s: float = 600.0
    #: exponential service lifetime (virtual seconds) after which the
    #: submitter deletes the pod whether it bound or not
    pod_lifetime_s: float = 240.0
    #: fraction of arrivals that are BE/batch-dim pods
    be_fraction: float = 0.25
    #: gang bursts: Poisson at this rate, each a gang of [lo, hi] pods
    gang_rate: float = 0.02
    gang_size: tuple[int, int] = (4, 16)
    #: node flaps: Poisson at this rate; a flapped node is DOWN for
    #: outage_s then comes back empty
    node_flap_rate: float = 0.01
    node_outage_s: float = 60.0
    #: quota churn: every interval one quota's max rescales within
    #: [squeeze, relax] of its base
    quotas: int = 4
    quota_churn_rate: float = 0.05
    quota_scale_range: tuple[float, float] = (0.4, 1.5)
    pod_cpu_milli: tuple[int, int] = (250, 2_000)
    pod_memory_mib: tuple[int, int] = (128, 2_048)
    #: multi-tenant traces (ISSUE 11): >1 emits one INDEPENDENT churn
    #: process per tenant — per-tenant seeds derive from the master
    #: seed (tenant_seed), every event carries a ``tenant`` field, and
    #: the harness replays each tenant's stream against its own cluster
    #: on a shared TenantScheduler mesh
    tenants: int = 1
    #: weighted-fair admission weights, one per tenant (short tuples
    #: pad with 1.0) — drives the TenantScheduler's DRR shares
    tenant_weights: tuple = ()

    def quota_names(self) -> list[str]:
        return [f"lg-quota-{i}" for i in range(self.quotas)]

    def tenant_names(self) -> list[str]:
        return [f"t{i}" for i in range(max(self.tenants, 1))]

    def tenant_weight(self, i: int) -> float:
        if i < len(self.tenant_weights):
            return float(self.tenant_weights[i])
        return 1.0


def tenant_seed(master_seed: int, tenant_index: int) -> int:
    """Per-tenant seed derived deterministically from the master seed:
    the SAME (master seed, tenant) pair always yields the same
    sub-trace, and tenant t's sub-trace is byte-identical to a
    single-tenant trace generated directly from this seed (asserted in
    tests/test_loadgen.py)."""
    return (master_seed * 1_000_003 + 7_919 * (tenant_index + 1)) \
        & 0x7FFFFFFF


def generate_trace(cfg: LoadGenConfig) -> list[Event]:
    """Expand a config (seed included) into the full sorted event list.

    Deterministic by construction: one ``random.Random(seed)`` drives
    every draw in a fixed order, so the same (seed, knobs) pair always
    yields the same byte-identical trace — the replay-seed discipline
    the chaos soak established.
    """
    if cfg.tenants > 1:
        return _generate_multi_tenant(cfg)
    rng = random.Random(cfg.seed)
    events: list[Event] = []
    pod_seq = 0
    gang_seq = 0

    def pod_payload(gang: str | None = None) -> dict:
        be = rng.random() < cfg.be_fraction
        return {
            "cpu": rng.randint(*cfg.pod_cpu_milli),
            "memory": rng.randint(*cfg.pod_memory_mib),
            "qos": 4 if be else 0,          # QoSClass.BE == 4
            "be": be,
            "priority": 0 if be else 1000,
            "gang": gang,
            "quota": rng.choice(cfg.quota_names()) if cfg.quotas else None,
        }

    def add_pod(t: float, gang: str | None = None) -> None:
        nonlocal pod_seq
        name = f"lg-p{pod_seq}"
        pod_seq += 1
        events.append(Event(t, POD_ADD, name, pod_payload(gang)))
        dead = t + rng.expovariate(1.0 / cfg.pod_lifetime_s)
        if dead < cfg.duration_s:
            events.append(Event(dead, POD_DEL, name))

    # -- pod arrivals: inhomogeneous Poisson by thinning ---------------------
    peak_rate = cfg.arrival_rate * (1.0 + abs(cfg.diurnal_amplitude))
    t = 0.0
    while peak_rate > 0:
        t += rng.expovariate(peak_rate)
        if t >= cfg.duration_s:
            break
        rate_t = cfg.arrival_rate * (
            1.0 + cfg.diurnal_amplitude
            * math.sin(2.0 * math.pi * t / cfg.diurnal_period_s))
        if rng.random() * peak_rate <= max(rate_t, 0.0):
            add_pod(t)

    # -- gang bursts ---------------------------------------------------------
    t = 0.0
    while cfg.gang_rate > 0:
        t += rng.expovariate(cfg.gang_rate)
        if t >= cfg.duration_s:
            break
        gang = f"lg-g{gang_seq}"
        gang_seq += 1
        size = rng.randint(*cfg.gang_size)
        events.append(Event(t, GANG_BURST, gang, {"size": size}))
        for _ in range(size):
            add_pod(t, gang=gang)

    # -- node flaps ----------------------------------------------------------
    t = 0.0
    down_until: dict[str, float] = {}
    while cfg.node_flap_rate > 0 and cfg.nodes > 0:
        t += rng.expovariate(cfg.node_flap_rate)
        if t >= cfg.duration_s:
            break
        node = f"lg-n{rng.randrange(cfg.nodes)}"
        if down_until.get(node, -1.0) >= t:
            continue                        # already down; skip this flap
        up_at = t + cfg.node_outage_s
        down_until[node] = up_at
        events.append(Event(t, NODE_DOWN, node))
        if up_at < cfg.duration_s:
            events.append(Event(up_at, NODE_UP, node))

    # -- quota churn ---------------------------------------------------------
    t = 0.0
    while cfg.quota_churn_rate > 0 and cfg.quotas > 0:
        t += rng.expovariate(cfg.quota_churn_rate)
        if t >= cfg.duration_s:
            break
        lo, hi = cfg.quota_scale_range
        events.append(Event(t, QUOTA_UPDATE, rng.choice(cfg.quota_names()),
                            {"scale": round(rng.uniform(lo, hi), 3)}))

    events.sort(key=lambda e: (e.t, e.kind, e.name))
    return events


def _generate_multi_tenant(cfg: LoadGenConfig) -> list[Event]:
    """One independent churn process per tenant, stamped and merged.

    Each tenant's sub-trace is ``generate_trace`` of the SAME knobs
    under its derived seed (so single-tenant determinism tests transfer
    verbatim); the merged stream sorts by (t, tenant, kind, name) for a
    stable, reproducible interleaving."""
    import dataclasses as _dc

    merged: list[Event] = []
    for i, name in enumerate(cfg.tenant_names()):
        sub = _dc.replace(cfg, seed=tenant_seed(cfg.seed, i), tenants=1)
        for e in generate_trace(sub):
            merged.append(Event(e.t, e.kind, e.name,
                                {**e.payload, "tenant": name}))
    merged.sort(key=lambda e: (e.t, e.payload.get("tenant", ""),
                               e.kind, e.name))
    return merged


def write_trace(events: Iterable[Event], path: str) -> None:
    with open(path, "w") as f:
        for e in events:
            f.write(json.dumps(e.to_doc()) + "\n")


def read_trace(path: str) -> list[Event]:
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                out.append(Event.from_doc(json.loads(line)))
    return out


def trace_stats(events: list[Event]) -> dict:
    counts: dict[str, int] = {}
    tenants: dict[str, int] = {}
    for e in events:
        counts[e.kind] = counts.get(e.kind, 0) + 1
        tenant = e.payload.get("tenant")
        if tenant is not None:
            tenants[tenant] = tenants.get(tenant, 0) + 1
    span = events[-1].t - events[0].t if len(events) > 1 else 0.0
    stats = {"events": len(events), "span_s": round(span, 3),
             "counts": counts,
             "arrival_rate": (round(counts.get(POD_ADD, 0) / span, 3)
                              if span > 0 else 0.0)}
    if tenants:
        stats["tenants"] = dict(sorted(tenants.items()))
    return stats


# ---------------------------------------------------------------------------
# Replay harness: scheduler sidecar + manager + feeder over real sockets
# ---------------------------------------------------------------------------

class SteadyStateHarness:
    """Drives a churn trace against the assembled control plane and
    watches it with the full observatory: SLO burn rates, self-telemetry
    sampling, and the long-horizon trend engine — all over ONE shared
    MetricCache with the two-tier downsampling horizon so a multi-hour
    soak stays memory-bounded.

    Socket scaffolding mirrors tests/test_chaos.py: an RpcServer on a
    unix socket hosts StateSyncService (+SchedulerBinding) and
    SolveService; a feeder client pushes node/pod events; a manager-side
    StateSyncClient + ColocationLoop watches and pushes batch
    allocatable back; a solver client drives rounds on a cadence.

    Leak injection (the harness must be able to catch itself lying):

    - ``inject_thread_leak`` — a toy service "handles" each cycle by
      spawning a thread that parks forever (released at close), the
      classic forgotten-worker leak; caught via koord_process_threads.
    - ``inject_queue_leak`` — pod deletions are dropped and solve
      rounds stop, so the admission queue only ever grows; caught via
      koord_scheduler_pending_pods.
    """

    def __init__(self, cfg: LoadGenConfig, workdir: str,
                 time_scale: float = 10.0,
                 solve_interval_s: float = 5.0,
                 sample_interval_s: float = 0.15,
                 trend_scale: float = 1.0,
                 slo_latency_threshold_s: float = 0.2,
                 warmup_fraction: float = 0.3,
                 inject_thread_leak: bool = False,
                 inject_queue_leak: bool = False,
                 quality_mode: str = "off",
                 quality_slack_threshold: float = 0.3):
        self.cfg = cfg
        self.workdir = workdir
        self.time_scale = time_scale
        self.solve_interval_s = solve_interval_s      # virtual seconds
        #: WALL seconds: trend fits run over real timestamps, and the
        #: sampler runs on its own thread so a blocking solve can't
        #: starve the observatory (the replay loop is single-threaded)
        self.sample_interval_s = sample_interval_s
        self.trend_scale = trend_scale
        #: the paper's p99 bar is 0.2; CPU smoke runs pass a looser one
        #: because their early rounds pay jit compilation in-line
        self.slo_latency_threshold_s = slo_latency_threshold_s
        #: the verdict's trend window opens after this fraction of the
        #: soak: the first rounds pay jit compilation and allocator
        #: warmup — real, one-time growth that a slope fit would read
        #: as a leak.  A true leak keeps leaking in the steady window.
        self.warmup_fraction = warmup_fraction
        self.steady_started_at: float | None = None
        self.inject_thread_leak = inject_thread_leak
        self.inject_queue_leak = inject_queue_leak
        #: solve-quality mode threaded into every scheduler the harness
        #: assembles (SOAK_QUALITY soaks run with "auto")
        self.quality_mode = quality_mode
        self.quality_slack_threshold = quality_slack_threshold
        self._leak_release = threading.Event()
        self._leaked_threads: list[threading.Thread] = []
        self._closers: list = []
        self.rounds = 0
        self.events_applied = 0
        self.push_errors = 0
        self.run_started_at: float | None = None
        self.scheduler = None
        self.monitor = None
        self.trend = None
        self.telemetry = None
        #: multi-tenant assembly (cfg.tenants > 1): the TenantScheduler
        #: front-end; per-tenant cluster stacks live in the maps below
        self.front = None
        self._feeders: dict = {}          # tenant -> feeder client
        self._tenant_sched: dict = {}     # tenant -> Scheduler
        self._quota_base: dict = {}       # (tenant, quota) -> base max
        self._colocations: list = []      # one ColocationLoop per cluster

    # -- assembly ------------------------------------------------------------

    def _build_quota_tree(self, tenant: str):
        import numpy as np

        from koordinator_tpu.api.resources import (
            NUM_RESOURCE_DIMS,
            resource_vector,
        )
        from koordinator_tpu.quota.tree import QuotaTree

        cfg = self.cfg
        total = resource_vector(
            cpu=cfg.node_cpu_milli * max(cfg.nodes, 1),
            memory=cfg.node_memory_mib * max(cfg.nodes, 1))
        quota_tree = QuotaTree(np.asarray(total, np.int64))
        for name in cfg.quota_names():
            qmax = (np.asarray(total, np.int64) * 2)
            quota_tree.add(name, min=np.zeros(NUM_RESOURCE_DIMS, np.int64),
                           max=qmax)
            self._quota_base[(tenant, name)] = qmax.copy()
        return quota_tree

    def _start_cluster(self, tenant: str, scheduler, index: int):
        """One cluster's socket stack: an RpcServer hosting a
        StateSyncService bound to THIS tenant's scheduler (the
        per-tenant sync binding — tenant isolation is structural: only
        this feed can make this tenant stale), a feeder client, and a
        manager-side watch + colocation loop.  Returns the server so
        the caller can mount the (shared) SolveService on cluster 0."""
        import numpy as np

        from koordinator_tpu.api.resources import resource_vector
        from koordinator_tpu.cmd.binaries import ReconnectingSidecarClient
        from koordinator_tpu.manager.colocation_loop import (
            ColocationLoop,
            ManagerSyncBinding,
            sidecar_push,
        )
        from koordinator_tpu.manager.noderesource_controller import (
            NodeResourceController,
        )
        from koordinator_tpu.transport import (
            RpcServer,
            StateSyncClient,
            StateSyncService,
        )
        from koordinator_tpu.transport.deltasync import SchedulerBinding
        from koordinator_tpu.transport.retry import RetryPolicy

        cfg = self.cfg
        sock = f"{self.workdir}/loadgen-{tenant}.sock"
        server = RpcServer(sock, service="scheduler")
        sync = StateSyncService(retention=8192)
        sync.attach(server)
        sync.attach_binding(SchedulerBinding(scheduler))
        server.start()
        self._closers.append(server.stop)

        retry = RetryPolicy(initial_backoff_s=0.05, max_backoff_s=0.5)
        feeder = ReconnectingSidecarClient(sock, retry_policy=retry,
                                           timeout=30.0)
        self._closers.append(feeder.close)
        self._feeders[tenant] = feeder
        self._tenant_sched[tenant] = scheduler

        binding = ManagerSyncBinding()
        mgr_sync = StateSyncClient(binding)

        def bootstrap_watch(client):
            mgr_sync.bind_client(client)
            mgr_sync.bootstrap(client)

        mgr_client = ReconnectingSidecarClient(
            sock, on_push=mgr_sync.on_push, on_connect=bootstrap_watch,
            retry_policy=retry, timeout=30.0)
        self._closers.append(mgr_client.close)

        self._colocations.append(ColocationLoop(
            NodeResourceController(), binding, sidecar_push(mgr_client),
            ensure_fn=mgr_client.ensure))

        # register the fleet directly on the sync service (the
        # informer-replay path the real binaries take at startup)
        alloc = np.asarray(resource_vector(
            cpu=cfg.node_cpu_milli, memory=cfg.node_memory_mib), np.int32)
        for i in range(cfg.nodes):
            sync.upsert_node(f"lg-n{i}", alloc)
        self._node_alloc = alloc
        if index == 0:
            self._server = server
            self._sync = sync
            self.feeder = feeder
            self.mgr_client = mgr_client
            self.mgr_sync = mgr_sync
        return server, sock

    def start(self) -> None:
        import numpy as np

        from koordinator_tpu.api.resources import resource_vector
        from koordinator_tpu.cmd.binaries import ReconnectingSidecarClient
        from koordinator_tpu.koordlet.metriccache import MetricCache
        from koordinator_tpu.scheduler import ClusterSnapshot, Scheduler
        from koordinator_tpu.selftelemetry import SelfTelemetry
        from koordinator_tpu.slo_monitor import (
            SloMonitor,
            default_specs,
            tenant_slo_specs,
        )
        from koordinator_tpu.transport.retry import RetryPolicy
        from koordinator_tpu.transport.services import SolveService
        from koordinator_tpu.transport.wire import FrameType
        from koordinator_tpu.trend import TrendEngine, default_trend_specs

        self._np = np
        self._resource_vector = resource_vector
        self._FrameType = FrameType

        cfg = self.cfg
        names = cfg.tenant_names()
        capacity = max(16, 1 << (cfg.nodes - 1).bit_length())
        # staleness is wall-clock: at time_scale compression the sync
        # feed beats every solve_interval/time_scale wall seconds, so
        # 8 beats of silence is a real stall, not compression artifact
        staleness = max(30.0, 8 * self.solve_interval_s / self.time_scale)
        if cfg.tenants > 1:
            from koordinator_tpu.scheduler.tenancy import (
                TenantScheduler,
                TenantSpec,
            )

            # the soak's budget is deliberately generous: the soak
            # proves steady state, the fairness tests prove sharing
            self.front = TenantScheduler(cycle_pod_budget=65_536)
            solve_target = self.front
            for i, name in enumerate(names):
                tenant = self.front.add_tenant(
                    TenantSpec(name=name, weight=cfg.tenant_weight(i),
                               node_capacity=capacity),
                    quota_tree=self._build_quota_tree(name),
                    staleness_threshold_sec=staleness,
                    quality_mode=self.quality_mode,
                    quality_slack_threshold=self.quality_slack_threshold)
                self._start_cluster(name, tenant.scheduler, i)
            self.scheduler = self.front.primary
        else:
            quota_tree = self._build_quota_tree(names[0])
            self.scheduler = Scheduler(
                ClusterSnapshot(capacity=capacity), quota_tree=quota_tree,
                staleness_threshold_sec=staleness,
                quality_mode=self.quality_mode,
                quality_slack_threshold=self.quality_slack_threshold)
            solve_target = self.scheduler
            self._start_cluster(names[0], self.scheduler, 0)
        sock0 = f"{self.workdir}/loadgen-{names[0]}.sock"
        SolveService(solve_target).attach(self._server)
        retry = RetryPolicy(initial_backoff_s=0.05, max_backoff_s=0.5)
        self.solver = ReconnectingSidecarClient(sock0, retry_policy=retry,
                                                timeout=240.0)
        self._closers.append(self.solver.close)

        # -- the observatory: one cache feeds SLO burn rates AND trends,
        # with the cold downsampling tier bounding an hours-long run
        cache = MetricCache(
            capacity_per_series=4096,
            retention_sec=max(4 * 3600.0, cfg.duration_s * 2),
            downsample_after_sec=600.0,
            downsample_resolution_sec=10.0)
        self.telemetry = SelfTelemetry("loadgen-harness")
        specs = default_specs(
            latency_threshold_s=self.slo_latency_threshold_s)
        if cfg.tenants > 1:
            # per-tenant p99 specs slice the shared latency histogram
            # by its {tenant=...} label (slo_monitor.tenant_slo_specs)
            specs = specs + tenant_slo_specs(
                names, latency_threshold_s=self.slo_latency_threshold_s)
        self.monitor = SloMonitor(
            specs=specs,
            cache=cache,
            sample_interval_s=self.sample_interval_s,
            on_breach=lambda spec, doc:
                self.scheduler.flight_recorder.dump_now(f"slo:{spec.name}"),
            pre_sample=[self.telemetry.sample])
        self.scheduler.slo_monitor = self.monitor
        self.trend = TrendEngine(cache,
                                 specs=default_trend_specs(
                                     scale=self.trend_scale),
                                 window_s=max(cfg.duration_s, 600.0))
        self.scheduler.trend_engine = self.trend
        if self.front is not None:
            self.front.slo_monitor = self.monitor
            self.front.trend_engine = self.trend

        # -- warm the solve path before the trend window opens (jit
        # compilation is one-time cost, not a trend): one warm pod per
        # tenant, one cycle, removal.  In quality mode the warm round
        # is forced onto the LP path too — auto's latch would otherwise
        # leave the quality program to compile mid-run, where its
        # (much larger) one-time cost reads as a latency breach and an
        # RSS step to the trend engine
        if self.quality_mode != "off":
            for sched in (self._tenant_sched.values()
                          if self._tenant_sched else [self.scheduler]):
                sched.arm_quality_escalation()
        for name in names:
            self._feeders[name].call(
                FrameType.STATE_PUSH,
                {"kind": "pod_add", "name": "lg-warm",
                 "priority": 1000},
                {"requests": np.asarray(resource_vector(
                    cpu=100, memory=64), np.int32)})
        self.solver.call(FrameType.SOLVE_REQUEST, {}, deadline_ms=240_000)
        for name in names:
            self._feeders[name].call(
                FrameType.STATE_PUSH,
                {"kind": "pod_remove", "name": "lg-warm"})
        for colocation in self._colocations:
            colocation.tick()
        self.colocation = self._colocations[0]

    # -- event application ---------------------------------------------------

    def _apply(self, event: Event) -> None:
        np = self._np
        rv = self._resource_vector
        FrameType = self._FrameType
        p = event.payload
        # tenant routing: every event lands on ITS tenant's feeder /
        # scheduler / quota tree (single-tenant traces carry no tenant
        # field and route to the only cluster)
        tenant = p.get("tenant", self.cfg.tenant_names()[0])
        feeder = self._feeders.get(tenant, self.feeder)
        scheduler = self._tenant_sched.get(tenant, self.scheduler)
        try:
            if event.kind == POD_ADD:
                if p.get("be"):
                    req = rv(batch_cpu=p["cpu"], batch_memory=p["memory"])
                else:
                    req = rv(cpu=p["cpu"], memory=p["memory"])
                doc = {"kind": "pod_add", "name": event.name,
                       "qos": int(p.get("qos", 0)),
                       "priority": int(p.get("priority", 0)),
                       # journey-ledger ingest stamp: rides the push as a
                       # sparse extras column so /debug/latency can split
                       # the feeder->enqueue hop out of e2e (ISSUE 20)
                       "arrival_ts": time.time()}
                if p.get("gang"):
                    doc["gang"] = p["gang"]
                if p.get("quota"):
                    doc["quota"] = p["quota"]
                feeder.call(FrameType.STATE_PUSH, doc,
                            {"requests": np.asarray(req, np.int32)})
            elif event.kind == POD_DEL:
                if self.inject_queue_leak:
                    return          # the leak: completions never arrive
                feeder.call(FrameType.STATE_PUSH,
                            {"kind": "pod_remove",
                             "name": event.name})
            elif event.kind == NODE_DOWN:
                feeder.call(FrameType.STATE_PUSH,
                            {"kind": "node_remove",
                             "name": event.name})
            elif event.kind == NODE_UP:
                feeder.call(
                    FrameType.STATE_PUSH,
                    {"kind": "node_upsert", "name": event.name},
                    {"allocatable": self._node_alloc})
            elif event.kind == GANG_BURST:
                # PodGroup CRs don't ride the node-state wire: register
                # the gang in-process before its members' pod_adds apply
                # (events sort gang_burst < pod_add at equal t)
                from koordinator_tpu.scheduler.scheduler import GangRecord

                scheduler.register_gang(GangRecord(
                    name=event.name, min_member=int(p["size"])))
            elif event.kind == QUOTA_UPDATE:
                # quota specs don't ride the wire (they are CRs, not
                # node state): churn them in-process under the round
                # lock, the webhook-update path's equivalent
                tree = scheduler.quota_tree
                base = self._quota_base.get((tenant, event.name))
                if tree is not None and base is not None:
                    with scheduler.lock:
                        node = tree.nodes.get(event.name)
                        if node is not None:
                            node.max = (base.astype(np.float64)
                                        * float(p.get("scale", 1.0))
                                        ).astype(np.int64)
            # GANG_BURST itself is a marker; its pods ride as POD_ADDs
            self.events_applied += 1
        except Exception:  # noqa: BLE001 — count-and-continue, the way
            self.push_errors += 1          # the real binaries ride out
            #                                a wedged peer tick

    def _solve_tick(self) -> None:
        try:
            self.solver.call(self._FrameType.SOLVE_REQUEST, {},
                             deadline_ms=240_000)
            self.rounds += 1
        except Exception:  # noqa: BLE001
            self.push_errors += 1
        for colocation in self._colocations:
            try:
                colocation.tick()
            except Exception:  # noqa: BLE001
                self.push_errors += 1
        self._maybe_leak_thread()

    def _maybe_leak_thread(self) -> None:
        """The injected leak: one forgotten worker per cycle, parked on
        the release event so close() can reap them all."""
        if self.inject_thread_leak:
            t = threading.Thread(target=self._leak_release.wait,
                                 daemon=True)
            t.start()
            self._leaked_threads.append(t)

    # -- replay --------------------------------------------------------------

    def run(self, events: list[Event],
            progress=None) -> dict:
        """Replay the trace at ``time_scale``x wall compression; solve
        rounds and observatory samples interleave on their own virtual
        cadences.  Returns the soak verdict document
        (:meth:`verdict`)."""
        start_wall = time.monotonic()
        self.run_started_at = time.time()
        warmup_vt = self.cfg.duration_s * self.warmup_fraction
        next_solve = 0.0
        i = 0
        vt_end = max(self.cfg.duration_s,
                     events[-1].t if events else 0.0)
        # sampling runs on the monitor's own wall-cadence thread: the
        # replay loop blocks on solves, and a starved sampler would
        # leave the trend window with too few points for any verdict
        self.monitor.start()
        try:
            while True:
                vt = (time.monotonic() - start_wall) * self.time_scale
                if self.steady_started_at is None and vt >= warmup_vt:
                    self.steady_started_at = time.time()
                while i < len(events) and events[i].t <= vt:
                    self._apply(events[i])
                    i += 1
                if vt >= next_solve:
                    if not self.inject_queue_leak:
                        self._solve_tick()
                    else:
                        self._solve_tick_starved()
                    next_solve += self.solve_interval_s
                    if progress is not None:
                        progress(vt, i, len(events))
                if vt >= vt_end and i >= len(events):
                    break
                time.sleep(0.02)
        finally:
            self.monitor.stop()
        self.monitor.tick()
        return self.verdict()

    def _solve_tick_starved(self) -> None:
        """The queue-leak variant: the arrival process keeps running but
        rounds stop serving it (a wedged solver), so pending_pods can
        only grow.  The gauge still needs refreshing — schedule_round
        normally publishes it — so read the queue depth directly."""
        from koordinator_tpu import metrics

        for scheduler in (self._tenant_sched.values()
                          if self._tenant_sched else [self.scheduler]):
            with scheduler.lock:
                depth = len(scheduler.pending)
            metrics.pending_pods.set(
                float(depth),
                labels=({"tenant": scheduler.tenant}
                        if scheduler.tenant else None))
        self._maybe_leak_thread()

    # -- verdict -------------------------------------------------------------

    def verdict(self, window_s: float | None = None) -> dict:
        """The soak's steady-state verdict: trend report (evaluated over
        the run window), SLO breach state, flight-recorder tallies, and
        the bounded-backlog/degraded-time checks the acceptance bar
        names."""
        from koordinator_tpu import metrics

        if window_s is None and self.steady_started_at is not None:
            # post-warmup steady window: jit compilation and allocator
            # ramp happened before it opened
            window_s = max(1.0, time.time() - self.steady_started_at)
        report = self.trend.evaluate(window_s=window_s)
        slo = self.monitor.report()
        rec = self.scheduler.flight_recorder
        tenants_doc = None
        if self.front is not None:
            tenants_doc = {}
            pending = bound = 0
            degraded = False
            records = dumps = overwrites = 0
            for tenant in self.front.tenants():
                sched = tenant.scheduler
                with sched.lock:
                    t_pending = len(sched.pending)
                    t_bound = len(sched.bound)
                    t_degraded = sched.degraded
                fr = sched.flight_recorder
                tenants_doc[tenant.name] = {
                    "weight": tenant.spec.weight,
                    "pending": t_pending,
                    "bound": t_bound,
                    "degraded": t_degraded,
                    "rounds": tenant.rounds,
                    "admitted_total": tenant.admitted_total,
                    "flight_dumps": fr.dumps,
                }
                pending += t_pending
                bound += t_bound
                degraded = degraded or t_degraded
                records += len(fr.records)
                dumps += fr.dumps
                overwrites += fr.overwrites
            flight = {"records": records, "dumps": dumps,
                      "overwrites": overwrites}
        else:
            with self.scheduler.lock:
                pending = len(self.scheduler.pending)
                bound = len(self.scheduler.bound)
                degraded = self.scheduler.degraded
            flight = {
                "records": len(rec.records),
                "dumps": rec.dumps,
                "overwrites": rec.overwrites,
            }
        doc = {
            "trend": report,
            "slo_breached": slo.get("breached", []),
            "slo": {d["name"]: {"breaches_total": d["breaches_total"],
                                "peak_burn": d["peak_burn"]}
                    for d in slo.get("slos", [])},
            "rounds": self.rounds,
            "events_applied": self.events_applied,
            "push_errors": self.push_errors,
            "pending": pending,
            "bound": bound,
            "degraded": degraded,
            "backlog_peak": metrics.sync_binding_backlog_peak.value(),
            "flight": flight,
            "green": (not report["leaking"] and not report["drifting"]
                      and not slo.get("breached") and not degraded),
        }
        if tenants_doc is not None:
            doc["tenants"] = tenants_doc
            doc["cycle"] = {
                "mode": self.front.last_mode,
                "host_wait_fraction": self.front.last_host_wait_fraction,
            }
        return doc

    def close(self) -> None:
        self._leak_release.set()
        for t in self._leaked_threads:
            t.join(timeout=5.0)
        self._leaked_threads.clear()
        if self.monitor is not None:
            self.monitor.stop()
        if self.telemetry is not None:
            self.telemetry.stop()
        for closer in reversed(self._closers):
            try:
                closer()
            except Exception:  # noqa: BLE001 — best-effort teardown
                pass
        self._closers.clear()


def smoke_config(seed: int = 0, tenants: int = 1) -> LoadGenConfig:
    """The small, fast, fixed shape the tier-1 smoke and the
    SOAK_LOADGEN=1 hook share: seconds of wall clock, every event kind
    exercised."""
    return LoadGenConfig(
        seed=seed,
        tenants=tenants,
        duration_s=120.0,
        nodes=24,
        node_cpu_milli=32_000,
        node_memory_mib=65_536,
        arrival_rate=1.5,
        diurnal_period_s=60.0,
        pod_lifetime_s=30.0,
        gang_rate=0.05,
        gang_size=(3, 6),
        node_flap_rate=0.03,
        node_outage_s=20.0,
        quotas=2,
        quota_churn_rate=0.08,
    )


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(
        prog="loadgen",
        description="generate (and inspect) deterministic churn traces; "
                    "tools/soak_report.py replays them against the live "
                    "control plane")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--duration", type=float, default=1800.0,
                        help="virtual seconds of churn")
    parser.add_argument("--nodes", type=int, default=10_000)
    parser.add_argument("--arrival-rate", type=float, default=8.0)
    parser.add_argument("--tenants", type=int, default=1,
                        help="emit one independent churn process per "
                             "tenant (tenant id on every event; "
                             "per-tenant seeds derive from --seed)")
    parser.add_argument("--out", default="",
                        help="write the trace as JSONL here")
    parser.add_argument("--stats", action="store_true",
                        help="print event-kind tallies for the trace")
    args = parser.parse_args(argv)
    cfg = LoadGenConfig(seed=args.seed, duration_s=args.duration,
                        nodes=args.nodes, arrival_rate=args.arrival_rate,
                        tenants=args.tenants)
    events = generate_trace(cfg)
    if args.out:
        write_trace(events, args.out)
        print(f"wrote {len(events)} events to {args.out}")
    if args.stats or not args.out:
        print(json.dumps(trace_stats(events), indent=2))
    return 0


if __name__ == "__main__":
    sys.path.insert(0, __file__.rsplit("/", 2)[0])
    raise SystemExit(main())
