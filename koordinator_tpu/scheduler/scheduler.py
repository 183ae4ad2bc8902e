"""The scheduling loop: batched solve rounds over the pending queue.

Where the reference's scheduleOne loop (SURVEY.md section 3.1) takes one pod per
cycle through PreFilter->Filter->Score->Reserve->Permit->PreBind->Bind, this
scheduler drains the whole pending queue through one batched TPU solve per
round:

  round():
    PreEnqueue   gang readiness + backoff gating (host)
    BatchBuild   pad pods to a power-of-two bucket, host affinity masks
    Solve        gang_assign (filter+score+assign+quota+gang) on device
    Reserve      adopt the solver's node accounting, charge quotas
    Bind         callback per placed pod
    Diagnose     structured reasons for every unplaced pod

Gang Permit semantics map to solve-and-rollback (ops/gang.py); the WaitTime
state machine survives here: a gang that keeps failing past its wait_time is
rejected and its pods surface failures (coscheduling core/gang.go WaitTime).
"""

from __future__ import annotations

import dataclasses
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from koordinator_tpu import journey, metrics, timeline, tracing
from koordinator_tpu.ops.assignment import ScoringConfig
from koordinator_tpu.ops.gang import GangInfo
from koordinator_tpu.ops.network_topology import (
    TopologyArrays,
    TopologyRequirements,
    plan_gang_placement,
)
from koordinator_tpu.quota.admission import QuotaDeviceState
from koordinator_tpu.ops.deviceshare import split_request
from koordinator_tpu.scheduler.device_manager import SOLVE_DEVICE_TYPE
from koordinator_tpu.quota.tree import QuotaTree
from koordinator_tpu.scheduler import bound_columns
from koordinator_tpu.scheduler.bound_columns import BoundRegistry
from koordinator_tpu.scheduler.diagnosis import PodDiagnosis, explain_pod
from koordinator_tpu.scheduler.monitor import SchedulerMonitor
from koordinator_tpu.scheduler.snapshot import ClusterSnapshot, PodSpec
from koordinator_tpu.state.cluster_state import PodBatch, _bucket

#: pending-queue key prefix for synthetic reserve-pods (the reference models
#: a Reservation as a pod the scheduler places; reservation_types.go)
RSV_POD_PREFIX = "rsv::"


@dataclasses.dataclass
class PdbRecord:
    """PodDisruptionBudget: selector + remaining disruption budget."""

    name: str
    selector: dict[str, str]
    allowed: int  # status.disruptionsAllowed

    def matches(self, labels: dict[str, str]) -> bool:
        # a PDB with an empty selector matches nothing; a pod with no labels
        # matches no PDB (filterPodsWithPDBViolation, preempt.go:224)
        if not self.selector or not labels:
            return False
        return all(labels.get(k) == v for k, v in self.selector.items())


@dataclasses.dataclass
class BoundPod:
    """Host record of a bound pod — the victim-candidate universe."""

    name: str
    node: str
    requests: np.ndarray
    priority: int = 0
    quota: str | None = None
    non_preemptible: bool = False
    labels: dict[str, str] = dataclasses.field(default_factory=dict)
    gang: str | None = None
    #: reservation this pod allocated from, and how much it drew — freeing
    #: the pod returns the drawn part to the reservation remainder (the node
    #: keeps the reservation's original charge), and unreserves only the
    #: spill that was charged to the node at bind time
    reservation: str | None = None
    rsv_drawn: np.ndarray | None = None
    rsv_generation: int = 0
    #: snapshot.node_generation at bind time: the node INSTANCE this
    #: pod's capacity was charged to — a release after the node was
    #: removed and re-added under the same name must not decrement the
    #: fresh instance (it starts clean; the churn suite drove
    #: node_requested negative before this stamp existed)
    node_generation: int = 0
    #: what the descheduler's view of the pod needs beyond the above
    #: (scheduler/bound_columns.py): QoS code, owning workload
    #: ("Kind/name"; a DaemonSet's pods are never evicted), annotations
    #: (the eviction-cost one) and whether the pod holds local storage
    qos: int = 0
    owner: str | None = None
    annotations: dict[str, str] = dataclasses.field(default_factory=dict)
    local_storage: bool = False


@dataclasses.dataclass
class GangRecord:
    """Host-side gang state (PodGroup + gang annotations)."""

    name: str
    min_member: int
    group: str | None = None
    #: None = inherit the scheduler's default (CoschedulingArgs
    #: DefaultTimeout via the component config; 600s like the reference)
    wait_time_sec: float | None = None
    first_failure: float | None = None
    rejected: bool = False
    #: network-topology gather requirements; needs Scheduler.topology_tree
    topology: TopologyRequirements | None = None


@dataclasses.dataclass
class SchedulingResult:
    assignments: dict[str, str]              # pod -> node
    failures: dict[str, PodDiagnosis]        # pod -> why
    round_pods: int = 0
    #: PostFilter outcomes: preemptor pod -> (nominated node, victim names)
    nominations: dict[str, tuple[str, list[str]]] = dataclasses.field(
        default_factory=dict
    )


def _wants_device(pod) -> bool:
    """Does the pod ask for a device (upstream's gpu-core / gpu-memory)?"""
    from koordinator_tpu.api.resources import ResourceDim

    return bool(pod.requests[ResourceDim.GPU] > 0
                or pod.requests[ResourceDim.GPU_MEMORY] > 0)


class _RoundGrants:
    """A round's device grants on the host, by batch row: the first
    solve's, with what the later passes and the rescue scan granted over
    their compacted rows merged in."""

    def __init__(self, selection: np.ndarray, lost_races: np.ndarray):
        self.selection = selection      # (P, D) bool
        self.lost_races = lost_races    # (P,) int32

    @classmethod
    def of(cls, grants) -> "_RoundGrants | None":
        """A solve's ``DeviceGrants`` (None: it carried no device stage)."""
        if grants is None:
            return None
        return cls(np.array(grants.selection), np.array(grants.lost_races))

    @staticmethod
    def merged(current: "_RoundGrants | None", grants, rows: np.ndarray,
               placed: np.ndarray, capacity: int) -> "_RoundGrants | None":
        """``current`` with the ``grants`` of a solve over the compacted
        ``rows`` of a batch of ``capacity`` merged in; ``placed`` marks
        the rows it assigned.  A first solve that granted nothing (a
        path without the device stage) starts from an empty sheet: the
        later solve's grants are on the device all the same."""
        if grants is None:
            return current
        if current is None:
            current = _RoundGrants(
                np.zeros((capacity, grants.selection.shape[1]), bool),
                np.zeros(capacity, np.int32))
        k = len(rows)
        current.lost_races[rows] += np.asarray(grants.lost_races)[:k]
        current.selection[rows[placed]] = np.asarray(
            grants.selection)[:k][placed]
        return current


@dataclasses.dataclass
class RoundHandle:
    """An in-flight round between its device and host halves (ISSUE 11).

    ``round_device`` returns one after DISPATCHING the solve; nothing in
    it has been blocked on.  ``assignments``/``new_quota`` are in-flight
    device arrays — the dispatched solve DONATED the previous
    ``snapshot.state`` buffers and the snapshot was re-pointed at the
    solve's in-flight state before dispatch returned (the blessed swap),
    so the pre-dispatch buffers are dead and must never be stashed on a
    handle.  The post-dispatch state is not stashed either: the host
    half reads it back from the snapshot, so a reserve or a release
    taken between the halves is in what it adopts, whether or not
    anything read the state in between.  The handle is only valid under
    the same ``scheduler.lock`` hold that produced it."""

    result: SchedulingResult
    #: the round finished entirely in the device half (elector/barrier
    #: gated, or an empty active queue) — round_host returns immediately
    done: bool = False
    now: float = 0.0
    pods: list = dataclasses.field(default_factory=list)
    batch: PodBatch | None = None
    gangs: GangInfo | None = None
    gang_index: dict = dataclasses.field(default_factory=dict)
    quota: object = None                 # post-prepass device quota
    solver: str = "greedy"
    assignments: object = None           # in-flight device array
    new_quota: object = None
    #: the solve's device grants (``ops/deviceshare.DeviceGrants``, in
    #: flight), None when it carried no device stage
    grants: object = None
    #: the solve's ``ops/assignment.ScanStats`` (in flight), None unless
    #: the exact greedy scan WAS the round's solve
    scan_stats: object = None
    #: incremental-path finish context (None = full/greedy path)
    inc: dict | None = None
    #: quality-path finish context (ISSUE 13): the LP solve's in-flight
    #: iteration count and pre-solve slack sums (None = not a quality
    #: round)
    quality: dict | None = None
    #: the forecast-headroom reserve charged into this round's solve
    #: (ISSUE 15; None = not a forecast round).  NOT donated — the host
    #: half's rescue pass re-charges the same tensor.
    forecast_reserve: object = None
    start_wall: float = 0.0
    t0: float = 0.0


class Scheduler:
    """Batched scheduler over a ClusterSnapshot."""

    def __init__(
        self,
        snapshot: ClusterSnapshot,
        config: ScoringConfig | None = None,
        quota_tree: QuotaTree | None = None,
        bind_fn=None,
        bind_batch_fn=None,
        monitor: SchedulerMonitor | None = None,
        gang_passes: int = 2,
        gang_default_timeout_sec: float = 600.0,
        batch_solver_threshold: int = 1024,
        clock=time.monotonic,
        topology_tree: TopologyArrays | None = None,
        barrier=None,
        debug_service=None,
        hints=None,
        enable_preemption: bool | None = None,
        preempt_fn=None,
        explanations=None,
        auditor=None,
        cpu_manager=None,
        device_manager=None,
        elector=None,
        incremental_solve: bool = True,
        staleness_threshold_sec: float | None = None,
        staleness_exit_sec: float | None = None,
        trace_pods: bool = False,
        faults=None,
        explain: bool = True,
        flight_ring_size: int = 256,
        tenant: str = "",
        solver_kit=None,
        quality_mode: str = "off",
        quality_slack_threshold: float = 0.3,
        forecast_mode: str = "off",
    ):
        self.snapshot = snapshot
        self.config = config if config is not None else ScoringConfig.default()
        self.quota_tree = quota_tree
        self.bind_fn = bind_fn
        #: batched bind sink (ISSUE 19): when set, each round's whole
        #: bind set arrives as ONE call ([(pod, node), ...]) — the seam
        #: for a single deltasync emission per round instead of one
        #: frame per pod.  bind_fn (per-pod) still fires when only it is
        #: set; a round with both set calls bind_batch_fn only.
        self.bind_batch_fn = bind_batch_fn
        #: tenancy identity (ISSUE 11): when set, this scheduler is one
        #: tenant of a TenantScheduler — per-tenant labels ride every
        #: scheduler metric, flight records stamp the tenant, and the
        #: front-end back-reference serves /debug/tenants
        self.tenant = tenant
        #: TenantScheduler back-reference (set by tenancy.add_tenant) so
        #: a per-tenant debug surface can serve the shared rollup
        self.tenant_front = None
        self.monitor = monitor or SchedulerMonitor()
        if tenant:
            self.monitor.tenant = tenant
        self.gang_passes = gang_passes
        #: CoschedulingArgs.DefaultTimeout: WaitTime for gangs that don't
        #: set their own
        self.gang_default_timeout_sec = gang_default_timeout_sec
        #: queues at or above this size solve with the data-parallel
        #: propose/accept engine instead of the exact sequential scan
        #: (ops/gang.py solver param) — exact for interactive queue sizes,
        #: batch-parallel at scale
        self.batch_solver_threshold = batch_solver_threshold
        self.clock = clock
        self.topology_tree = topology_tree

        #: startup sync barrier (barrier.SyncBarrier) — rounds no-op until
        #: the informer replays past it
        self.barrier = barrier
        #: debug service for top-N score dumps (services.DebugService)
        self.debug_service = debug_service
        #: scheduling hints (hints.SchedulingHints) — mask edits per pod
        self.hints = hints
        #: explanation.ExplanationStore — failures persist as
        #: ScheduleExplanation CRs (schedule_diagnosis.go DumpDiagnosis)
        self.explanations = explanations
        #: explanation.WorkloadAuditor — per-pod/gang lifecycle records
        self.auditor = auditor
        self.last_result = SchedulingResult({}, {}, 0)
        #: which solve engine the last round used ("greedy"/"batch")
        self.last_solver = "greedy"
        #: serializes rounds against informer-driven mutations — the
        #: transport layer applies watch pushes from a reader thread while
        #: solve RPCs run rounds (the reference relies on the upstream
        #: single-scheduling-goroutine + informer snapshot model)
        self.lock = threading.RLock()
        self.pending: dict[str, PodSpec] = {}
        self.gangs: dict[str, GangRecord] = {}
        # PodBatch cache: repeated rounds over an unchanged pending queue
        # (pods parked on gangs/quota, failing pods awaiting capacity) reuse
        # the previous device batch instead of rebuilding host-side
        self._pending_rev = 0
        self._batch_cache: tuple[tuple, PodBatch] | None = None
        self.batch_rebuilds = 0
        #: host-side arrays of the last batch build, for row-level reuse
        #: when the queue changes incrementally (see _build_batch)
        self._batch_host: dict | None = None
        #: (capacity,) rows of this round's batch that the reservation
        #: pre-pass settled (bound, or a reserve-pod); None when it
        #: settled none.  The incremental dispatch takes it
        self._prepass_settled: np.ndarray | None = None
        # -- the solver kit --
        # every jitted entry point lives in a SolverKit, which alone
        # decides where a solve runs (mesh or one device), with which
        # candidate method and parameters: a standalone scheduler builds
        # its own, a tenant of a TenantScheduler is handed the
        # front-end's shared kit so T tenants multiplex onto ONE compiled
        # solver (one jit cache, one recompile ledger)
        from koordinator_tpu.scheduler.solver_kit import SolverKit

        self.kit = solver_kit if solver_kit is not None else SolverKit()
        self.snapshot.set_state_placement(self.kit.place)

        # -- incremental delta-driven solve (no-gang batch rounds) --
        #: steady-state rounds refresh a device-resident (P, k) candidate
        #: cache against the dirty-node/pod delta instead of re-selecting
        #: over the whole (P, N) problem; falls back to the full pass when
        #: the dirty fraction crosses incremental_dirty_threshold
        self.incremental_solve = incremental_solve
        self.incremental_dirty_threshold = 0.25
        self._cand_cache: dict | None = None
        #: which candidate path the last batch round took
        #: (incremental | full_cold | full_fallback | full_gang |
        #: full_dense | disabled)
        self.last_solve_path = "none"
        #: stable per-pod-name rotation ids (PodBatch.rot_id): a pod keeps
        #: its candidate tie-break rotation when the queue shifts around it
        self._rot_ids: dict[str, int] = {}
        self._rot_counter = 0
        self._align_cands = self.kit.align_cands
        self._scatter_cands = self.kit.scatter_cands

        # -- solve-quality mode (ISSUE 13) --
        #: "off" = today's greedy path exactly; "lp" = every eligible
        #: round solves with the LP-relaxation packing engine
        #: (quality/lp_pack); "auto" = escalate only rounds whose
        #: preceding result leaves capacity_slack_fraction above the
        #: threshold (free capacity is the win-back opportunity
        #: constraint-based packing exists for)
        from koordinator_tpu.quality import QUALITY_MODES

        if quality_mode not in QUALITY_MODES:
            raise ValueError(f"unknown quality_mode {quality_mode!r}; "
                             f"one of {QUALITY_MODES}")
        self.quality_mode = quality_mode
        self.quality_slack_threshold = quality_slack_threshold
        #: auto-mode escalation latch, recomputed from every round's
        #: resulting per-dim slack (MIN over provisioned dims vs the
        #: threshold: every dimension must have headroom worth winning
        #: back — see _quality_round_finish)
        self._quality_escalate = False
        self._last_quality_iters = 0
        metrics.solver_quality_mode.set(
            float(QUALITY_MODES.index(quality_mode)),
            labels=self._tl())

        # -- forecast plane (ISSUE 15) --
        #: "off" = today's solve exactly (the forecast entries are never
        #: called — bit-identical acceptance decisions and quota
        #: charges); "admit" = the forecast-headroom reserve charges
        #: into every eligible round's filter/score accounting; "full" =
        #: admission plus the colocation/rebalance drivers armed at
        #: assembly.  The plane itself attaches separately
        #: (attach_forecast_plane) — a mode without a plane is inert.
        from koordinator_tpu.forecast import FORECAST_MODES

        if forecast_mode not in FORECAST_MODES:
            raise ValueError(f"unknown forecast_mode {forecast_mode!r}; "
                             f"one of {FORECAST_MODES}")
        self.forecast_mode = forecast_mode
        self.forecast_plane = None
        #: per-round admission cap (tenancy weighted-fair admission sets
        #: it per cycle; None = admit the whole active queue).  Applied
        #: in priority order AFTER the PreEnqueue gates, so a capped
        #: round still schedules the most important pods first.
        self.round_pod_limit: int | None = None
        #: pods held back by the cap in the last round (fairness surface)
        self.last_overflow = 0
        #: PodBatch capacity floor: the tenant-axis batched solve stacks
        #: several tenants' batches on a leading axis, which needs every
        #: tenant padded to the SAME pod bucket
        self.batch_capacity_floor = 0
        #: reservation lifecycle (plugins/reservation parity): reserve-pods
        #: schedule through the normal rounds, Available sets get a
        #: reservation-first exact solve pre-pass
        from koordinator_tpu.scheduler.reservations import ReservationCache

        self.reservations = ReservationCache()
        self._rsv_solve = self.kit.rsv_solve
        #: fine-grained allocators (nodenumaresource / deviceshare Reserve):
        #: LSR/LSE pods take exclusive cpusets, device requests take minors
        #: at bind; annotation payloads surface in resource_status
        self.cpu_manager = cpu_manager
        self.device_manager = device_manager
        if device_manager is not None:
            # the device books move into the snapshot's node rows, and
            # the GPU plane rides the solve (``ClusterState.devices``)
            self.snapshot.attach_devices(device_manager)
        #: per-node vendor device-plugin lock annotations (the node-object
        #: annotation in the reference; vendors' plugins clear it via
        #: clear_device_node_lock when they finish a pod)
        self._device_node_locks: dict[str, dict[str, str]] = {}
        self.resource_status: dict[str, dict] = {}
        #: quota overuse revoke controller (enable_overuse_revoke)
        self.overuse_revoke = None
        #: ha.LeaderElector — rounds no-op while not leading (the reference
        #: leader-elects the whole scheduling loop, server.go)
        self.elector = elector
        #: bound on pods routed through the sequential reservation pre-pass
        #: per round — a popular owner selector must not drag a 50k-pod
        #: round onto the O(P) exact scan (extras solve normally and can
        #: draw reservations next round)
        self.rsv_prepass_cap = 2048
        self._rsv_match_cache: tuple[tuple, np.ndarray] | None = None

        # -- preemption (PostFilter) state --
        # default: only preempt when someone is wired to actually evict the
        # victim (otherwise the scheduler would free accounting for pods that
        # keep running, double-booking nodes)
        self.enable_preemption = (
            enable_preemption if enable_preemption is not None
            else preempt_fn is not None
        )
        #: called as preempt_fn(victim_name, preemptor_name) on each eviction
        self.preempt_fn = preempt_fn
        #: name -> BoundPod, and the same pods as columns
        #: (``self.bound.columns``) for preemption and the descheduler
        self.bound: BoundRegistry = BoundRegistry(self.snapshot.dims)
        self.pdbs: dict[str, PdbRecord] = {}
        #: preemptor pod -> nominated node name (nominatedNodeName semantics)
        self.nominations: dict[str, str] = {}
        #: node INSTANCE each nomination's charge was assumed against
        #: (snapshot.node_generation at assume time)
        self._nomination_gen: dict[str, int] = {}
        self._preempt = self.kit.preempt
        self._preempt_chain = self.kit.preempt_chain
        #: bound on PostFilter work per round (mirror of rsv_prepass_cap):
        #: at most this many failed pods attempt preemption in one round —
        #: a quota-starved 50k queue must not turn PostFilter into 50k
        #: device calls (upstream bounds the preemption cycle's work the
        #: same way, coscheduling preemption.go:206).  Excess pods stay
        #: pending and retry next round.
        self.preempt_cap = 1024
        #: single-pod preemptors are chained in jitted scans of this size
        #: (one dispatch per chunk, not per pod); gangs use the host loop
        self.preempt_chunk = 256

        # -- snapshot-staleness watchdog / degraded mode --
        #: seconds the sync feed may be silent before rounds flip into
        #: degraded mode; None disables the watchdog.  Constraint-based
        #: packing only keeps its guarantees against fresh-or-conservative
        #: state: a stalled delta feed means usage/allocatable (and the
        #: manager-derived batch capacity riding them) are arbitrarily
        #: stale, so degraded rounds (a) suspend BE/batch-dim admission —
        #: the consumers of the stale-derived overcommit capacity — and
        #: (b) drop the incremental candidate cache and solve full-pass
        #: until the feed re-warms.
        self.staleness_threshold_sec = staleness_threshold_sec
        #: hysteresis: exit degraded only once the feed age is back under
        #: this (default threshold/2) so a feed trickling right at the
        #: threshold doesn't flap admission on and off
        self.staleness_exit_sec = staleness_exit_sec
        self.degraded = False
        self.degraded_since: float | None = None
        self.degraded_entries = 0
        #: pods held out of the last round by degraded-mode suspension
        self.last_suspended = 0

        # -- tracing + round flight recorder --
        from koordinator_tpu.scheduler.flight_recorder import FlightRecorder

        #: trace EVERY enqueued pod (a root span per pod) even without a
        #: propagated context.  Off by default: per-pod spans are O(P)
        #: host work per round, and untraced operation should pay one
        #: round span, not 50k — a caller-propagated TraceContext (the
        #: wire path) always traces its pod regardless of this flag.
        self.trace_pods = trace_pods
        #: live trace context per pending pod (the enqueue span); popped
        #: when the pod binds (the bind span parents to it) or leaves
        self.pod_traces: dict[str, tracing.TraceContext] = {}
        #: bounded pod-name -> trace_id registry surviving bind, for
        #: /debug/trace/<pod> lookups
        self._pod_trace_ids: dict[str, str] = {}
        self.round_seq = 0
        #: ring capacity is a knob (--flight-ring-size): a long soak's
        #: report joins verdicts to rounds, so the ring must hold enough
        #: rounds to cover the report's window — size it so
        #: round_flight_overwritten_total stays near zero over the run
        self.flight_recorder = FlightRecorder(
            capacity=flight_ring_size,
            slow_threshold_s=self.monitor.timeout_sec)
        #: device-side share of the round's solve (time blocked on
        #: jitted results), accumulated across solve dispatches
        self._solve_device_s = 0.0
        #: dispatch-half wall carried into the host half's single
        #: "Solve" phase observation (pipelined round split, ISSUE 11)
        self._solve_carry_s = 0.0
        self._last_dirty_node_frac = 0.0
        self._last_dirty_pod_frac = 0.0
        self._last_staleness_s: float | None = None
        self._round_recordable = False
        #: journey-ledger solve-dispatch edge; None outside a round so
        #: out-of-round binds fall back to their own commit stamp
        self._journey_round_t0: float | None = None

        # -- placement explainability (ISSUE 6) --
        from koordinator_tpu.scheduler.explanation import ExplanationRing

        #: kill switch (--no-explain): when False the Diagnose phase
        #: falls back to the per-pod host recompute, no explanations are
        #: retained, and the unschedulability rollups stay silent
        self.explain = explain
        self._explain_counts = self.kit.explain_counts
        self._slack_sums = self.kit.slack_sums
        #: bounded pod-keyed retention behind /debug/explain/<pod>
        self.explain_ring = ExplanationRing()
        #: {top reason -> pod count} rollup of the last round (flight
        #: recorder + unschedulable_pods gauge source)
        self._last_unschedulable_top: dict[str, int] = {}
        #: pods _active_pods held out this round, for explanation
        #: recording (suspension / rejected gangs happen before Diagnose
        #: ever sees the pod)
        self._last_suspended_names: list[str] = []
        self._last_gang_rejected_names: list[str] = []

        # -- self-observability (ISSUE 5) --
        #: chaos-harness fault injector (transport.faults.FaultInjector);
        #: the Solve phase consults on_solve() when attached — None (the
        #: default) costs one attribute check per round
        self.faults = faults
        #: SloMonitor attached by the binary assembly (serves /debug/slo
        #: and fires flight-recorder dumps on fast-burn breaches)
        self.slo_monitor = None
        #: trend.TrendEngine attached by the binary assembly (serves
        #: /debug/steady: steady/drifting/leaking verdicts over the
        #: self-telemetry and queue-depth series); None => typed 501
        self.trend_engine = None
        #: introspection.ProfilerCapture behind /debug/profile; None =
        #: the endpoint answers 403 (gated off by default)
        self.profile_capture = None

    def stop(self) -> None:
        """Assembly-level teardown (Assembled.stop): stops the attached
        SLO sampler thread when one is running."""
        if self.slo_monitor is not None:
            self.slo_monitor.stop()

    def attach_forecast_plane(self, plane) -> None:
        """Install the forecast plane (forecast/plane.ForecastPlane):
        grown to the snapshot's capacity and pinned under the solver
        mesh's node sharding when one is active, so the admission
        reserve and the charged solve never reshard.  The round prelude
        feeds it (observe + cadenced refresh) whenever
        ``forecast_mode != "off"``."""
        with self.lock:
            if plane.capacity < self.snapshot.capacity:
                plane.grow(self.snapshot.capacity)
            if self.kit.sharding_active_for(self.snapshot.capacity):
                plane.set_sharding(self.kit.node_sharding)
            plane.metric_labels = dict(self._tl() or {})
            self.forecast_plane = plane

    def _forecast_reserve(self):  # koordlint: guarded-by(self.lock)
        """The round's (N, R) forecast-headroom reserve, or None when
        forecasting is off / the plane is absent or not yet refreshed —
        the predicate every forecast branch keys on, so ``off`` never
        touches a forecast entry."""
        if self.forecast_mode == "off" or self.forecast_plane is None:
            return None
        return self.forecast_plane.admission_reserve(self.snapshot.state)

    # -- registration -------------------------------------------------------

    def register_gang(self, record: GangRecord) -> None:
        with self.lock:
            if record.wait_time_sec is None:
                record.wait_time_sec = self.gang_default_timeout_sec
            self.gangs[record.name] = record

    def register_pdb(self, record: PdbRecord) -> None:
        with self.lock:
            self.pdbs[record.name] = record

    def add_bound_pod(self, pod: BoundPod,
                      resource_status: dict | None = None) -> None:
        """Seed a pre-existing bound pod (informer replay at startup).

        Owns the accounting: the pod's request is reserved on its node here,
        and released by :meth:`remove_bound_pod` — callers never touch the
        snapshot directly, so a pod the scheduler already evicted (popped
        from ``bound``) cannot be double-freed by a late informer delete.

        ``resource_status`` replays the pod's fine-grained annotations
        ({"resource-status": {"cpuset": "0,1"}, "device-allocated": {...}})
        into the CPU/device managers so restart can't re-grant pinned cores
        or in-use device minors to new pods."""
        with self.lock:
            self.bound[pod.name] = pod
            if pod.node in self.snapshot.node_index:
                self.snapshot.reserve(pod.node, pod.requests)
            if resource_status:
                self._restore_fine_grained(pod, resource_status)

    def _restore_fine_grained(self, pod: BoundPod, status: dict) -> None:
        """Annotations are persisted external data: a malformed or stale
        payload (topology changed across restart) skips that pod's restore
        instead of crashing the informer replay."""
        rs = status.get("resource-status") or {}
        cpuset = rs.get("cpuset", "") if isinstance(rs, dict) else ""
        if cpuset and self.cpu_manager is not None:
            from koordinator_tpu.scheduler.cpu_manager import (
                EXCLUSIVE_PCPU_LEVEL,
                parse_cpuset_bounded,
            )

            try:
                cpus = parse_cpuset_bounded(str(cpuset))
            except ValueError:
                cpus = []
            if cpus and self.cpu_manager.restore(
                    pod.node, pod.name, cpus,
                    exclusive_policy=EXCLUSIVE_PCPU_LEVEL):
                self.resource_status.setdefault(pod.name, {})[
                    "resource-status"] = rs
        devices = status.get("device-allocated") or {}
        if devices and self.device_manager is not None:
            if self.device_manager.restore(pod.node, pod.name, devices):
                # serve the RE-DERIVED truth, not the raw payload: a
                # partially-restored annotation (unknown types, stale
                # minors) must not be reported as tracked
                self.resource_status.setdefault(pod.name, {})[
                    "device-allocated"] = (
                        self.device_manager.device_allocated_annotation(
                            pod.node, pod.name))

    def remove_bound_pod(self, name: str) -> None:
        """Release a bound pod's node reservation iff still tracked (quota
        stays with the caller: eviction paths release it themselves).

        A pod that allocated through a reservation gives its drawn vector
        back to the reservation remainder (the reserved capacity stays
        charged to the node, hidden from non-owners) and frees only its
        spill; once the reservation is gone/consumed, the drawn backing
        charge frees with the pod.

        Host work only: the freed vector joins the snapshot's pending
        delta, and the next read of ``snapshot.state`` (the next round's
        dispatch, as a rule) folds every release since the last one into
        ``node_requested`` in one device op."""
        with self.lock:
            pod = self.bound.pop(name, None)
            if pod is not None:
                self._release_bound_capacity(pod)

    def _release_bound_capacity(self, bp: BoundPod) -> None:
        """Shared freeing for a bound pod leaving the cluster (informer
        delete, eviction, preemption): fine-grained allocations, then the
        reservation-aware node unreserve.  Both spans time host work
        alone; the unreserve's device op is the snapshot's next fold
        (span ``snapshot.fold``)."""
        tl_t0 = timeline.RECORDER.open("release.fine_grained")
        self._release_fine_grained(bp.name, bp.node)
        timeline.RECORDER.close(tl_t0, "host_other", self.tenant)
        tl_t1 = time.perf_counter()
        self._unreserve_bound(bp)
        timeline.RECORDER.add(tl_t1, time.perf_counter(), "host_other",
                              "release.unreserve", self.tenant)

    def _unreserve_bound(self, bp: BoundPod) -> None:
        """The reservation-aware node unreserve of a leaving bound pod."""
        if bp.node not in self.snapshot.node_index:
            return
        if (self.snapshot.node_generation.get(bp.node, 0)
                != bp.node_generation):
            # the node this pod was charged to is GONE; the same name now
            # labels a fresh instance that started clean — decrementing
            # it would drive node_requested negative (the reservation
            # drawn/spill split below also died with the old instance)
            return
        free_vec = bp.requests
        if bp.reservation is not None and bp.rsv_drawn is not None:
            drawn = bp.rsv_drawn.astype(np.int64)
            if self.reservations.return_allocation(
                    bp.reservation, drawn, bp.rsv_generation):
                free_vec = np.maximum(
                    bp.requests.astype(np.int64) - drawn, 0)
            else:
                free_vec = np.maximum(
                    bp.requests.astype(np.int64), drawn)
        self.snapshot.unreserve(bp.node, free_vec.astype(np.int32))

    def delete_pod(self, name: str) -> None:
        """Informer pod delete, whatever state the pod is in: a pending or
        nominated pod is dequeued; a bound pod releases BOTH its node
        reservation and its quota charge (the _commit_bind mirror)."""
        with self.lock:
            if name in self.pending or name in self.nominations:
                self.dequeue(name)
            bound = self.bound.get(name)
            if bound is not None:
                self.remove_bound_pod(name)
                self._charge_quota_used(bound, sign=-1)

    def set_pod_usage(self, names, usage: np.ndarray) -> None:
        """Per-pod real usage, (k, R) for the bound pods ``names``: the
        descheduler's victim universe reads it from the bound pods'
        columns.  There is no wire kind for it; the embedding deployment
        sets it.  A pod none was set for reads its request."""
        with self.lock:
            self.bound.columns.set_usage(names, usage)

    def enable_overuse_revoke(self, revoke_fn,
                              delay_evict_sec: float = 5.0) -> None:
        """Turn on the elastic-quota overuse revoke loop
        (quota_overuse_revoke.go): each round, quotas whose used exceeds
        runtime continuously past the delay get their least-important pods
        revoked until they fit.  ``revoke_fn(pod, quota)`` is REQUIRED —
        it performs the external eviction; the scheduler's own accounting
        releases here, and freeing capacity no one actually evicts would
        oversubscribe the node."""
        from koordinator_tpu.quota.overuse_revoke import (
            QuotaOveruseRevokeController,
        )

        self.overuse_revoke = QuotaOveruseRevokeController(
            self, revoke_fn=revoke_fn, delay_evict_sec=delay_evict_sec,
            clock=self.clock)

    def add_reservation(self, spec) -> None:
        """Accept a Reservation CR: placement happens next round (a pinned
        node goes Available directly; otherwise a synthetic reserve-pod
        schedules through the normal solve).

        Re-applying an existing name is an update: if the placed charge is
        unchanged (same requests, same pin) only the mutable spec fields
        move; otherwise the old reservation is removed first (returning its
        remainder) so the new one can't double-charge the node."""
        from koordinator_tpu.scheduler.reservations import ReservationPhase

        with self.lock:
            spec.created_at = self.clock()
            old = self.reservations.get(spec.name)
            if old is not None and old.phase in (
                ReservationPhase.AVAILABLE, ReservationPhase.SUCCEEDED
            ):
                if (np.array_equal(old.requests, spec.requests)
                        and spec.node in (None, old.node)):
                    old.owners = spec.owners
                    old.ttl_sec = spec.ttl_sec
                    old.restricted = spec.restricted
                    # owner edits change who matches: drop the cached
                    # owner-match matrix (generation stays — bind records
                    # against this instance remain valid)
                    self._rsv_match_cache = None
                    return
                self.remove_reservation(spec.name)
            self.reservations.upsert(spec)
            # a still-queued reserve-pod carries the OLD requests vector;
            # drop it so the next tick re-enqueues the updated one
            if self.pending.pop(RSV_POD_PREFIX + spec.name, None) is not None:
                self._pending_rev += 1

    def remove_reservation(self, name: str) -> None:
        """Reservation CR deleted: return the unallocated remainder and drop
        any in-flight reserve-pod."""
        with self.lock:
            self.reservations.remove(name, self.snapshot)
            if self.pending.pop(RSV_POD_PREFIX + name, None) is not None:
                self._pending_rev += 1

    def _reservation_tick(self, now: float) -> None:  # koordlint: guarded-by(self.lock)
        """Expire reservations; move Pending ones toward Available (pinned
        node: direct, with a fit check; else enqueue a reserve-pod)."""
        for name in self.reservations.fail_stale_instances(self.snapshot):
            if self.auditor is not None:
                self.auditor.record(name, "ReservationFailed",
                                    "node instance gone")
        for name in self.reservations.expire_tick(now, self.snapshot):
            # a Pending reservation that expired drops its reserve-pod too
            if self.pending.pop(RSV_POD_PREFIX + name, None) is not None:
                self._pending_rev += 1
            if self.auditor is not None:
                self.auditor.record(name, "ReservationExpired", "")
        # terminal specs are settled accounting-wise; purge so long-running
        # schedulers don't pay an ever-growing Reservations tick
        self.reservations.gc()
        for spec in self.reservations.pending():
            if spec.node is not None:
                # pre-pinned: goes Available only if it actually fits —
                # make_available charges the node, and an over-committed
                # charge would block the node for everyone (the un-pinned
                # path gets this fit check from the reserve-pod solve)
                row = self.snapshot.node_index.get(spec.node)
                if row is None:
                    continue
                free = (
                    np.asarray(self.snapshot.state.node_allocatable[row])
                    - np.asarray(self.snapshot.state.node_requested[row])
                )
                if np.all(spec.requests <= free):
                    self.reservations.make_available(
                        spec.name, spec.node, self.snapshot, now)
                continue
            key = RSV_POD_PREFIX + spec.name
            if key not in self.pending:
                self.pending[key] = PodSpec(
                    name=key, requests=spec.requests.astype(np.int32),
                    priority=9000, node_selector=dict(spec.node_selector),
                    tolerations=dict(spec.tolerations))
                self._pending_rev += 1

    def _reservation_prepass(self, pods, batch, quota, result):  # koordlint: guarded-by(self.lock)
        """Reservation-first exact solve (plugin.go Reserve + nominator
        semantics) over two kinds of pods.  Owner-matched pods allocate
        from their reservation's remainder before the general solve sees
        them.  Reserve-pods are placed here too, by the same exact pass:
        it carries the LoadAware estimate of every pod it places onto the
        next one, which the batch engine's rounds do not, so a reservation
        is only opened on a node that will still admit its owner — however
        many reservations one round places.  (Through the batch engine a
        round of several hundred reserve-pods piled dozens onto the
        emptiest node, and the exact pass then turned their owners away
        from it.)  Returns the (possibly shrunk) batch and quota."""
        avail = self.reservations.available()
        # fully-consumed reservations have nothing to lend — skip their
        # O(P) host-side owner matching
        lendable = any(np.any(s.requests > s.allocated) for s in avail
                       if s.allocated is not None)
        reserve_pods = np.zeros(batch.capacity, bool)
        # only a Pending reservation has a reserve-pod in the queue: with
        # none, the queue is not walked
        if self.reservations.pending():
            reserve_pods[: len(pods)] = [
                p.name.startswith(RSV_POD_PREFIX) for p in pods]
        if not lendable and not reserve_pods.any():
            return batch, quota
        rsv_set, names = self.reservations.build_set(self.snapshot)
        # the O(pods x reservations) python owner matching is cached
        # between rounds over an unchanged queue + reservation set (the
        # PodBatch cache analog): steady-state rounds pay a dict lookup
        # the key must cover everything the matrix depends on: the active
        # pod ROW ORDER (gang rejection shrinks _active_pods without
        # bumping _pending_rev), and reservation identity/owners (owner
        # edits clear the cache in add_reservation)
        mkey = (self._pending_rev,
                tuple(p.name for p in pods),
                tuple(s.generation for s in avail))
        cached = self._rsv_match_cache
        if not lendable:
            match = np.zeros((batch.capacity, rsv_set.capacity), bool)
        elif cached is not None and cached[0] == mkey:
            match = cached[1]          # read-only below: no defensive copy
        else:
            match = self.reservations.match_matrix(
                pods, batch.capacity, rsv_set.capacity)
            # reserve-pods can't consume reservations; gang members keep
            # all-or-nothing semantics in the main solve
            for i, pod in enumerate(pods):
                if pod.name.startswith(RSV_POD_PREFIX) or pod.gang:
                    match[i] = False
            self._rsv_match_cache = (mkey, match)
        matched = np.asarray(batch.valid) & (match.any(axis=1)
                                             | reserve_pods)
        if not matched.any():
            return batch, quota
        if int(matched.sum()) > self.rsv_prepass_cap:
            prio = np.asarray(batch.priority)
            rows = np.flatnonzero(matched)
            keep = rows[np.argsort(-prio[rows], kind="stable")
                        [: self.rsv_prepass_cap]]
            matched = np.zeros_like(matched)
            matched[keep] = True
        small, idx = batch.compact(matched)
        m_small = np.zeros((small.capacity, rsv_set.capacity), bool)
        m_small[: len(idx)] = match[idx]
        a_r, rc, new_state, _, new_quota, scanned = self._rsv_solve(
            self.snapshot.state, small, self.config, rsv_set,
            jnp.asarray(m_small), quota)
        a_r, rc = np.asarray(a_r), np.asarray(rc)
        self._prepass_scan = self._count_scan(len(idx), scanned)
        self.snapshot.adopt_state(new_state,
                                  changed_rows=np.unique(a_r[a_r >= 0]))
        sub_pods = [pods[i] for i in idx]
        drawn = self.reservations.commit_allocations(names, sub_pods, a_r, rc)
        bound_rows = [int(idx[j]) for j in range(len(sub_pods))
                      if int(a_r[j]) >= 0]
        now = self.clock()
        for j, pod in enumerate(sub_pods):
            if int(a_r[j]) >= 0 and pod.name.startswith(RSV_POD_PREFIX):
                self._commit_reserve_pod(
                    pod, self.snapshot.node_name(int(a_r[j])), result, now)
            elif int(a_r[j]) >= 0:
                r = int(rc[j])
                rname = (names[r] if 0 <= r < len(names)
                         and drawn[j] is not None else None)
                rspec = (self.reservations.get(rname)
                         if rname is not None else None)
                self._commit_bind(
                    pod, self.snapshot.node_name(int(a_r[j])), result,
                    reservation=rname, rsv_drawn=drawn[j],
                    rsv_generation=(rspec.generation if rspec else 0))
        # settled here, so out of the general solve: the pods bound above,
        # and every reserve-pod — one this exact pass found no node for, or
        # one past the cap, waits in the queue for the next round's
        # pre-pass; the batch engine never places one
        settled = reserve_pods.copy()
        settled[bound_rows] = True
        if settled.any():
            batch = batch.replace(valid=batch.valid & ~jnp.asarray(settled))
            self._prepass_settled = settled
        return batch, (new_quota if new_quota is not None else quota)

    # koordlint: guarded-by(self.lock)
    def _commit_reserve_pod(self, pod: PodSpec, node: str,
                            result: SchedulingResult, now: float) -> None:
        """The reserve-pod 'bound': its Reservation becomes Available.  The
        solve already charged the reserved vector to node_requested, so no
        further snapshot accounting (make_available charges only on the
        pinned-node path, which bypasses the solve)."""
        from koordinator_tpu.scheduler.reservations import ReservationPhase

        rname = pod.name[len(RSV_POD_PREFIX):]
        if self.pending.pop(pod.name, None) is not None:
            self._pending_rev += 1
        spec = self.reservations.get(rname)
        if spec is None:
            # CR deleted mid-round: release the solve's charge
            self.snapshot.unreserve(node, pod.requests)
            return
        # the solve already charged the reserved vector: open without
        # re-charging (the shared transition keeps both paths identical)
        self.reservations.make_available(
            rname, node, self.snapshot, now=now, charge=False)
        result.assignments[pod.name] = node
        if self.explanations is not None:
            self.explanations.delete(pod.name)
        if self.auditor is not None:
            self.auditor.record(pod.name, "ReservationAvailable", node)

    def enqueue(self, pod: PodSpec) -> None:
        with self.lock:
            self._enqueue_locked(pod)

    def enqueue_many(self, pods: list[PodSpec]) -> None:
        """Admit a batch under ONE lock acquisition (ISSUE 19): the
        deltasync binding routes contiguous pod_add runs here so a
        loadgen burst costs one lock round-trip, not one per pod.
        Per-pod semantics (arrival accounting, trace roots, pending
        revision bumps) are exactly the sequential loop's."""
        if not pods:
            return
        with self.lock:
            for pod in pods:
                self._enqueue_locked(pod)

    def _enqueue_locked(self, pod: PodSpec) -> None:  # koordlint: guarded-by(self.lock)
        tl_t0 = time.perf_counter()
        # arrival-process accounting (ISSUE 9): rate() of this is
        # the admission rate the churn load generator drives.  Only
        # NEW names count — a resync bootstrap replays pod_add for
        # every still-pending pod, and re-counting the whole queue
        # would paint a phantom arrival spike on the dashboards
        if pod.name not in self.pending:
            metrics.pods_enqueued_total.inc(labels=self._tl())
            # journey-ledger enqueue stamp (ISSUE 20): first enqueue only
            # — a resync replay must not reset the pod's queue-wait clock
            if journey.LEDGER.enabled:
                journey.LEDGER.note_enqueue(
                    pod.name, getattr(pod, "arrival_ts", 0.0))
        self.pending[pod.name] = pod
        self._pending_rev += 1
        # the pod's trace starts (or joins) here: a propagated
        # context (wire push applying under tracing.activate) always
        # traces; trace_pods opts untraced pods into root spans.
        # Synthetic reserve-pods are placement vehicles, not user
        # workloads — they stay untraced like they stay unaudited.
        ctx = tracing.current_context()
        if ((ctx is not None or self.trace_pods)
                and not pod.name.startswith(RSV_POD_PREFIX)):
            sp = tracing.TRACER.start_span(
                "scheduler.enqueue", service="scheduler", parent=ctx,
                attributes={"pod": pod.name,
                            "priority": int(pod.priority)})
            sp.end()
            self.pod_traces[pod.name] = sp.context()
            self._register_pod_trace(pod.name, sp.trace_id)
        timeline.RECORDER.add(tl_t0, time.perf_counter(), "host_other",
                              "enqueue", self.tenant)

    def _register_pod_trace(self, name: str, trace_id: str) -> None:
        """Bounded name -> trace_id map for /debug/trace/<pod>: survives
        bind (the interesting queries are about bound pods), trimmed
        oldest-first so a years-long scheduler doesn't leak."""
        ids = self._pod_trace_ids
        ids.pop(name, None)          # re-enqueue refreshes recency
        ids[name] = trace_id
        if len(ids) > 8192:
            for key in list(ids)[: len(ids) // 2]:
                del ids[key]

    def pod_trace_id(self, name: str) -> str | None:
        """Most recent trace_id recorded for a pod (debug surface)."""
        return self._pod_trace_ids.get(name)

    def dequeue(self, pod_name: str) -> None:
        # a deleted nominated preemptor must release its assumed reservation
        # and quota charge, and must not pin a future same-named pod
        with self.lock:
            pod = self.pending.pop(pod_name, None)
            self.pod_traces.pop(pod_name, None)
            if pod is not None:
                self._pending_rev += 1
                journey.LEDGER.forget(pod_name)
            if pod_name in self.nominations and pod is not None:
                self._nomination_release(pod)
            else:
                self.nominations.pop(pod_name, None)
                self._nomination_gen.pop(pod_name, None)

    # -- snapshot-staleness watchdog ----------------------------------------

    def note_sync_event(self) -> None:
        """An informer/sync event was applied: the state feed is alive.
        Called by the deltasync dispatch layer (remote watch client and
        in-process binding drain alike)."""
        self.snapshot.mark_sync(self.clock())

    def _staleness_tick(self, now: float) -> None:  # koordlint: guarded-by(self.lock)
        """Flip degraded mode on/off from the sync feed's age.  Runs at
        round start under the round lock."""
        threshold = self.staleness_threshold_sec
        age = self.snapshot.staleness(now)
        self._last_staleness_s = age   # flight-recorder surface
        if threshold is None or age is None:
            # watchdog disabled, or no feed has ever spoken (a scheduler
            # warming up has nothing to be stale RELATIVE to)
            return
        metrics.state_staleness_seconds.set(age, labels=self._tl())
        if not self.degraded and age > threshold:
            self.degraded = True
            self.degraded_since = now
            self.degraded_entries += 1
            # the candidate cache was built from now-untrusted deltas;
            # degraded rounds solve full-pass and re-warm on exit
            self._cand_cache = None
            metrics.degraded_mode.set(1.0, labels=self._tl())
            metrics.degraded_transitions_total.inc(
                labels={"phase": "enter", **(self._tl() or {})})
        elif self.degraded:
            exit_thr = (self.staleness_exit_sec
                        if self.staleness_exit_sec is not None
                        else threshold / 2.0)
            if age <= exit_thr:
                self.degraded = False
                self.degraded_since = None
                self._cand_cache = None
                metrics.degraded_mode.set(0.0, labels=self._tl())
                metrics.degraded_transitions_total.inc(
                    labels={"phase": "exit", **(self._tl() or {})})

    def _suspended_while_degraded(self, pod: PodSpec) -> bool:
        """Admission suspended for this pod while degraded?  BE pods and
        any pod consuming batch/mid dims: those pools are DERIVED from
        the (now stale) usage reports, so admitting against them is how
        a stale scheduler overcommits real machines.  Prod pods keep
        scheduling — their allocatable is configured, not derived.
        Reserve-pods ride along normally (a Reservation's charge is
        validated against allocatable at placement like any prod pod)."""
        from koordinator_tpu.api.qos import QoSClass
        from koordinator_tpu.api.resources import BATCH_DIMS, MID_DIMS

        if pod.name.startswith(RSV_POD_PREFIX):
            return False
        if int(pod.qos) == int(QoSClass.BE):
            return True
        req = np.asarray(pod.requests)
        return bool(any(int(req[d]) > 0 for d in (*BATCH_DIMS, *MID_DIMS)))

    # -- the scheduling round ----------------------------------------------

    def _active_pods(self) -> list[PodSpec]:
        """PreEnqueue: skip pods of rejected gangs; while degraded, hold
        back BE/batch-dim pods (stale-state admission suspension)."""
        out = []
        suspended = 0
        self._last_suspended_names = []
        self._last_gang_rejected_names = []
        for pod in self.pending.values():
            if pod.gang is not None:
                gang = self.gangs.get(pod.gang)
                if gang is not None and gang.rejected:
                    if not pod.name.startswith(RSV_POD_PREFIX):
                        self._last_gang_rejected_names.append(pod.name)
                    continue
            if self.degraded and self._suspended_while_degraded(pod):
                suspended += 1
                if not pod.name.startswith(RSV_POD_PREFIX):
                    self._last_suspended_names.append(pod.name)
                continue
            out.append(pod)
        self.last_suspended = suspended
        metrics.degraded_suspended_pods.set(float(suspended),
                                            labels=self._tl())
        out.sort(key=lambda p: (-p.priority, p.creation, p.name))
        # weighted-fair admission cap (tenancy, ISSUE 11): a capped
        # round admits only its share of the cycle's pod budget —
        # highest-priority first, the overflow stays pending and is
        # charged to nobody (it retries next cycle with fresh credits)
        limit = self.round_pod_limit
        if limit is not None and len(out) > max(limit, 0):
            self.last_overflow = len(out) - max(limit, 0)
            out = out[: max(limit, 0)]
        else:
            self.last_overflow = 0
        return out

    def _build_batch(self, pods: list[PodSpec], gang_index: dict[str, int],
                     quota_index: dict[str, int]) -> PodBatch:
        hinted = self.hints is not None and any(
            self.hints.has_hint(pod.name) for pod in pods
        )
        # cache key: anything that feeds the batch tensors. pending_rev
        # covers pod contents (mutations go through enqueue/dequeue), the
        # name tuple covers active-set changes (gang parking/rejection),
        # capacity covers node-array growth, class_count covers new label/
        # taint equivalence classes (node->class reassignment to an existing
        # class flows through ClusterState.node_class, not the batch)
        key = (
            self._pending_rev,
            tuple(pod.name for pod in pods),
            tuple(sorted(gang_index.items())),
            tuple(sorted(quota_index.items())),
            self.snapshot.capacity,
            self.snapshot.class_count,
            self.batch_capacity_floor,
            self._has_devices(),
        )
        if (not hinted and self._batch_cache is not None
                and self._batch_cache[0] == key):
            return self._batch_cache[1]
        p = len(pods)
        # the tenant-axis batched solve stacks several tenants' batches
        # on a leading axis, so every tenant pads to the SAME bucket
        # (batch_capacity_floor; 0 for a standalone scheduler)
        cap = _bucket(max(p, self.batch_capacity_floor, 1), minimum=16)
        n_cap = self.snapshot.capacity
        requests = np.zeros((p, self.snapshot.dims), np.int32)
        priority = np.zeros(p, np.int32)
        qos = np.zeros(p, np.int8)
        gang_id = np.full(p, -1, np.int32)
        quota_id = np.full(p, -1, np.int32)
        non_preempt = np.zeros(p, bool)
        rot = np.zeros(p, np.int32)

        # stable rotation ids: a pod keeps its candidate tie-break when
        # the queue shifts around it (the incremental candidate cache's
        # row-independence depends on this).  The registry is pruned
        # against the live queue so a years-long scheduler doesn't leak.
        if len(self._rot_ids) > 4 * max(len(self.pending), 64):
            live = set(self.pending)
            self._rot_ids = {name: rid for name, rid in
                             self._rot_ids.items() if name in live}
        for i, pod in enumerate(pods):
            rid = self._rot_ids.get(pod.name)
            if rid is None:
                rid = self._rot_ids[pod.name] = self._rot_counter
                # 31-bit wrap: the id is a tie-break rotation identity
                # (modular by construction), and an unbounded counter
                # would overflow the int32 rot tensor after ~2.1e9
                # distinct pod names in one process lifetime
                self._rot_counter = (self._rot_counter + 1) & 0x7FFFFFFF
            rot[i] = rid

        # row-level reuse from the previous build: an incremental queue
        # change (the steady-state delta) re-fills only the rows whose
        # pod is new or re-specced; unchanged rows gather from the last
        # build's host arrays in one vectorized copy.  Only valid when
        # the id mappings and selector-mask width are unchanged — they
        # parameterize row CONTENT.
        c_cap = self.snapshot.class_capacity
        prev = self._batch_host if not hinted else None
        reuse_ok = (
            prev is not None
            and prev["gang_index"] == gang_index
            and prev["quota_index"] == quota_index
            and prev["class_cap"] == c_cap
            # class COUNT, not just the padded width: a new equivalence
            # class within the same bucket changes every pod's selector
            # row content (the new class's column)
            and prev["class_count"] == self.snapshot.class_count
            and prev["dims"] == self.snapshot.dims
        )
        sel = np.zeros((p, c_cap), bool) if not hinted else None
        fill_rows: list[int] = []
        if reuse_ok:
            src, dst = [], []
            prev_row, prev_spec = prev["row_of"], prev["specs"]
            for i, pod in enumerate(pods):
                j = prev_row.get(pod.name)
                if j is not None and prev_spec.get(pod.name) is pod:
                    src.append(j)
                    dst.append(i)
                else:
                    fill_rows.append(i)
            if dst:
                src_a, dst_a = np.asarray(src), np.asarray(dst)
                requests[dst_a] = prev["requests"][src_a]
                priority[dst_a] = prev["priority"][src_a]
                qos[dst_a] = prev["qos"][src_a]
                gang_id[dst_a] = prev["gang_id"][src_a]
                quota_id[dst_a] = prev["quota_id"][src_a]
                non_preempt[dst_a] = prev["non_preempt"][src_a]
                sel[dst_a] = prev["sel"][src_a]
        else:
            fill_rows = list(range(p))

        memo: dict[tuple, np.ndarray] = {}
        for i in fill_rows:
            pod = pods[i]
            requests[i] = pod.requests
            priority[i] = pod.priority
            qos[i] = pod.qos
            if pod.gang is not None and pod.gang in gang_index:
                gang_id[i] = gang_index[pod.gang]
            if pod.quota is not None and pod.quota in quota_index:
                quota_id[i] = quota_index[pod.quota]
            non_preempt[i] = pod.non_preemptible
            if sel is not None:
                sel_key = (
                    tuple(sorted(pod.node_selector.items())),
                    tuple(sorted(pod.tolerations.items())),
                )
                row = memo.get(sel_key)
                if row is None:
                    row = self.snapshot.selector_row_for(pod)
                    memo[sel_key] = row
                sel[i] = row

        # placement constraints: factored O(P·C) equivalence-class masks by
        # default; the dense O(P·N) path only when a pod carries per-node
        # hint edits (rare — skip/prefer hints from the hinter)
        if hinted:
            feasible = np.zeros((p, n_cap), bool)
            for i, pod in enumerate(pods):
                row = self.snapshot.feasibility_row(pod)
                feasible[i] = self.hints.apply_to_mask(pod.name, row)
            mask_kw = dict(feasible=feasible)
        else:
            mask_kw = dict(selector_mask=sel, class_capacity=c_cap)
        batch = PodBatch.build(
            requests, priority=priority, qos=qos, gang_id=gang_id,
            quota_id=quota_id, non_preemptible=non_preempt,
            node_capacity=n_cap, capacity=cap, rot_id=rot, **mask_kw,
        )
        if not hinted:
            # reused across steady-state rounds: the kit pins it where
            # its sharded entries read it in place
            batch = self.kit.place_batch(batch, n_cap,
                                         devices=self._has_devices())
            self._batch_cache = (key, batch)
            self._batch_host = {
                "row_of": {pod.name: i for i, pod in enumerate(pods)},
                "specs": {pod.name: pod for pod in pods},
                "requests": requests, "priority": priority, "qos": qos,
                "gang_id": gang_id, "quota_id": quota_id,
                "non_preempt": non_preempt, "sel": sel,
                "gang_index": dict(gang_index),
                "quota_index": dict(quota_index),
                "class_cap": c_cap,
                "class_count": self.snapshot.class_count,
                "dims": self.snapshot.dims,
            }
        self.batch_rebuilds += 1
        return batch

    def _has_devices(self) -> bool:
        """Does the state the next solve meets carry a device plane?"""
        return self.snapshot.resident_state.devices is not None

    def _build_gang_info(self, pods: list[PodSpec]) -> tuple[GangInfo, dict[str, int]]:
        names = sorted({p.gang for p in pods if p.gang is not None})
        index = {n: i for i, n in enumerate(names)}
        groups: dict[str, int] = {}
        min_member = np.zeros(max(len(names), 1), np.int32)
        group_id = np.arange(max(len(names), 1), dtype=np.int32)
        for name, i in index.items():
            gang = self.gangs.get(name)
            min_member[i] = gang.min_member if gang else 0
            if gang and gang.group:
                group_id[i] = groups.setdefault(gang.group, i)
        return (
            GangInfo.build(min_member[: len(names)], group_id[: len(names)])
            if names else GangInfo.build(np.zeros(0, np.int32)),
            index,
        )

    def _refresh_quota_tree(self) -> None:
        """GroupQuotaManager duty: a leaf quota's request is what its pods
        ask for — already-admitted usage plus this round's pending requests
        — then re-derive runtime (fingerprint-cached in the tree)."""
        pending: dict[str, np.ndarray] = {}
        for pod in self.pending.values():
            if pod.quota is not None and pod.quota in self.quota_tree.nodes:
                cur = pending.setdefault(
                    pod.quota, np.zeros(self.snapshot.dims, np.int64)
                )
                cur += pod.requests.astype(np.int64)
        for name, qnode in self.quota_tree.nodes.items():
            if self.quota_tree.children[name]:
                continue  # parents aggregate from children
            self.quota_tree.set_request(
                name, qnode.used + pending.get(
                    name, np.zeros(self.snapshot.dims, np.int64))
            )
        self.quota_tree.refresh_runtime()

    def _build_quota(self) -> tuple[QuotaDeviceState | None, dict[str, int]]:
        if self.quota_tree is None:
            return None, {}
        self._refresh_quota_tree()
        return QuotaDeviceState.from_tree(self.quota_tree)

    # koordlint: guarded-by(self.lock)
    def _apply_topology_plans(
        self, batch: PodBatch, gang_index: dict[str, int]
    ) -> PodBatch:
        """FindOneNode parity (``frameworkext/interface.go:120``,
        ``coscheduling.go:137-144``): a gang with network-topology
        requirements gets a placement plan up front; each member's feasible
        set is pinned to its planned node. A gang whose plan fails is masked
        out of the round entirely (all-or-nothing at plan level)."""
        if self.topology_tree is None:
            return batch
        # densifying the factored mask is O(P·N): skip it entirely unless
        # some gang in this round actually carries topology requirements
        if not any(
            self.gangs.get(name) is not None
            and self.gangs[name].topology is not None
            for name in gang_index
        ):
            return batch
        gang_ids = np.asarray(batch.gang_id)
        feasible = np.array(batch.feasible_rows(self.snapshot.state))
        valid = np.array(batch.valid)
        changed = False
        for name, gi in gang_index.items():
            gang = self.gangs.get(name)
            if gang is None or gang.topology is None:
                continue
            mask = (gang_ids == gi) & valid
            if not mask.any():
                continue
            # quality mode swaps in the rank-aware, topology-distance
            # planner (quality/topo_gang): same feasibility kernels,
            # minimal-diameter commit rule
            if self.quality_mode != "off":
                from koordinator_tpu.quality.topo_gang import (
                    plan_gang_placement_quality,
                )

                plan_fn = plan_gang_placement_quality
            else:
                plan_fn = plan_gang_placement
            plan = plan_fn(
                self.snapshot.state, batch, mask, self.topology_tree,
                gang.topology, cfg=self.config,
            )
            changed = True
            desired = gang.topology.desired_slots or int(mask.sum())
            planned = np.flatnonzero(mask & (plan >= 0))
            if len(planned) < min(desired, int(mask.sum())):
                # no gather plan at all -> the whole gang backs off
                valid[mask] = False
                continue
            # pin planned members; surplus members (pending > desired_slots)
            # stay unpinned and schedule freely once the gang is permitted
            feasible[planned] = False
            feasible[planned, plan[planned]] = True
        if not changed:
            return batch
        # topology pinning needs per-(pod, node) edits: densify the mask
        return batch.replace(
            feasible=jnp.asarray(feasible), valid=jnp.asarray(valid),
            selector_mask=None,
        )

    def _tl(self) -> dict | None:
        """Per-tenant metric labels; None for an untenanted scheduler so
        its series (and every existing dashboard/test) are unchanged."""
        return {"tenant": self.tenant} if self.tenant else None

    def _round_begin(self) -> None:  # koordlint: guarded-by(self.lock)
        """Reset the per-round accumulators (shared by the serial
        schedule_round wrapper and the pipelined round_device entry)."""
        self.round_seq += 1
        self.monitor.start_round()
        self._solve_device_s = 0.0
        self._solve_carry_s = 0.0
        #: first dispatch edge of the round (timeline device-busy
        #: derivation); consumed by the first block edge
        self._tl_device_t0 = None
        self._last_dirty_node_frac = 0.0
        self._last_dirty_pod_frac = 0.0
        self._last_unschedulable_top = {}
        #: (rows handed over, loop steps taken) of the round's exact
        #: scans: the rescue pass, the reservation pre-pass
        self._rescue_scan = self._prepass_scan = (0, 0)
        self._round_recordable = False
        #: solve-dispatch edge for the journey ledger's queue_wait/solve
        #: stage split — round-scoped: set here, read by the bind-commit
        #: paths, cleared again when the host half returns so an
        #: out-of-round bind never inherits a previous round's edge
        self._journey_round_t0 = time.perf_counter()

    def _current_path(self) -> str:
        return (self.last_solve_path
                if self.last_solver == "batch" else "greedy")

    # koordlint: guarded-by(self.lock)
    def _round_flight_record(self, result: SchedulingResult, trace_id: str,
                             start_wall: float, duration: float,
                             path: str, half: str) -> None:
        from koordinator_tpu.scheduler.flight_recorder import RoundRecord

        self.flight_recorder.record(RoundRecord(
            round=self.round_seq,
            trace_id=trace_id,
            start_time=start_wall,
            duration_s=duration,
            solver=self.last_solver,
            solve_path=path,
            pods=result.round_pods,
            placed=len(result.assignments),
            failed=len(result.failures),
            suspended=self.last_suspended,
            degraded=self.degraded,
            staleness_s=self._last_staleness_s,
            dirty_node_frac=self._last_dirty_node_frac,
            dirty_pod_frac=self._last_dirty_pod_frac,
            solve_wall_s=self.monitor.round_timings.get(
                "Solve", 0.0),
            solve_device_s=self._solve_device_s,
            phase_s=dict(self.monitor.round_timings),
            sheds_total=metrics.solve_deadline_shed_total.value(),
            top_unschedulable=dict(self._last_unschedulable_top),
            tenant=self.tenant,
            half=half,
            quality_mode=self.quality_mode,
            quality_iterations=self._last_quality_iters,
            rescue_rows=self._rescue_scan[0],
            rescue_steps=self._rescue_scan[1],
            prepass_rows=self._prepass_scan[0],
            prepass_steps=self._prepass_scan[1],
        ))

    def schedule_round(self) -> SchedulingResult:
        """Solve the current pending queue; reserve, bind, diagnose.

        Every round runs inside a ``scheduler.round`` span (joined to
        the caller's trace when one rode the solve request) whose
        attributes double as the round's flight record; rounds that got
        past the elector/barrier gates land in the flight recorder ring
        (``/debug/rounds``), slow/degraded ones dump automatically.

        The round is internally split into an explicit DEVICE half
        (:meth:`_round_device`: prelude, batch build, solve dispatch)
        and HOST half (:meth:`_round_host`: block, rescue, commit,
        diagnose); this serial wrapper runs them back to back under one
        lock hold, while the tenancy front-end drives
        :meth:`round_device`/:meth:`round_host` directly so round N+1's
        device solve overlaps round N's host commit."""
        with self.lock:
            self._round_begin()
            start_wall = time.time()
            t0 = time.perf_counter()
            with tracing.TRACER.span(
                    "scheduler.round", service="scheduler",
                    attributes={"round": self.round_seq}) as span:
                result = self._round_host(self._round_device())
                duration = time.perf_counter() - t0
                path = self._current_path()
                if not self._round_recordable:
                    # elector-standby / barrier-gated: last_solver and
                    # last_solve_path are STALE leftovers of the last
                    # deciding round — stamping them here would claim a
                    # solve that never ran
                    span.set_attributes({"gated": True})
                else:
                    span.set_attributes({
                        "solver": self.last_solver,
                        "solve_path": path,
                        "pods": result.round_pods,
                        "placed": len(result.assignments),
                        "failed": len(result.failures),
                        "suspended": self.last_suspended,
                        "degraded": self.degraded,
                        "staleness_s": self._last_staleness_s,
                        "dirty_node_frac": self._last_dirty_node_frac,
                        "dirty_pod_frac": self._last_dirty_pod_frac,
                        "solve_wall_s": self.monitor.round_timings.get(
                            "Solve", 0.0),
                        "solve_device_s": self._solve_device_s,
                    })
            if self._round_recordable:
                self._round_flight_record(result, span.trace_id,
                                          start_wall, duration, path,
                                          half="round")
            if self._round_recordable:
                # after the last monitor phase: a span of its own, or the
                # round's tail (a device reduction and its wait) has no
                # name; the pipelined path has round.commit around it
                with timeline.RECORDER.section(
                        "host_other", "round.introspection", self.tenant):
                    self._publish_round_introspection()
            if (self._round_recordable and self.tenant_front is None
                    and timeline.RECORDER.enabled):
                # an untenanted scheduler's round IS its cycle: the
                # timeline observatory reconstructs/attributes the same
                # window the tenancy front-end would (ISSUE 18)
                doc = timeline.RECORDER.finish_cycle(
                    self.round_seq, t0, time.perf_counter(),
                    mode="round")
                if doc is not None:
                    self.flight_recorder.annotate_round(
                        self.round_seq, self.tenant,
                        cycle_seq=doc["cycle"],
                        cycle_critical_cause=doc["critical_cause"],
                        cycle_critical_seconds=doc["critical_seconds"])
            return result

    def round_device(self) -> "RoundHandle":
        """Public DEVICE-half entry for pipelined operation (the tenancy
        front-end).  The caller MUST hold ``self.lock`` across the
        ``round_device`` -> ``round_host`` pair — the handle references
        in-flight donated state, and an informer mutation between the
        halves would solve one queue and commit another.  Each half
        leaves its own flight record (``half="solve"``/``"commit"``) so
        ``/debug/rounds`` attributes slow halves to a tenant."""
        start_wall = time.time()
        t0 = time.perf_counter()
        # blanket the device half as lowest-priority host work: the
        # typed segments inside (build_batch, dispatch, lock_wait) win
        # the sweep; only the inter-phase glue lands here instead of in
        # the unattributed residual
        with timeline.RECORDER.section("host_other", "round.prepare",
                                       self.tenant):
            self._round_begin()
            with tracing.TRACER.span(
                    "scheduler.round.solve", service="scheduler",
                    attributes={"round": self.round_seq,
                                "tenant": self.tenant}) as span:
                handle = self._round_device()
        handle.start_wall = start_wall
        handle.t0 = t0
        if self._round_recordable and not handle.done:
            self._round_flight_record(
                handle.result, span.trace_id, start_wall,
                time.perf_counter() - t0, self._current_path(),
                half="solve")
        return handle

    def round_host(self, handle: "RoundHandle") -> SchedulingResult:
        """Public HOST-half entry: block on the dispatched solve and
        commit.  Pairs with :meth:`round_device` under one lock hold."""
        # blanket the host half like round_device does: block waits keep
        # their device_block priority, commit glue stops leaking into
        # the unattributed residual
        with timeline.RECORDER.section("host_other", "round.commit",
                                       self.tenant):
            with tracing.TRACER.span(
                    "scheduler.round.commit", service="scheduler",
                    attributes={"round": self.round_seq,
                                "tenant": self.tenant}) as span:
                result = self._round_host(handle)
            if self._round_recordable:
                self._round_flight_record(
                    result, span.trace_id, handle.start_wall,
                    time.perf_counter() - handle.t0, self._current_path(),
                    half="commit")
                self._publish_round_introspection()
        return result

    # koordlint: guarded-by(self.lock)
    def _publish_round_introspection(self) -> None:
        # device-resident footprint of the persistent solver
        # tensors, from array metadata only (no sync): the
        # live-bytes half of the introspection surface
        from koordinator_tpu.ops import introspection as insp

        metrics.solver_device_bytes.set(
            float(insp.device_bytes(self.snapshot.state)),
            labels={"kind": "cluster_state"})
        cand = self._cand_cache
        metrics.solver_device_bytes.set(
            float(insp.device_bytes(
                cand["cache"] if cand else None)),
            labels={"kind": "candidate_cache"})
        # sharded-solve introspection: the active nodes-axis
        # width plus the per-device slice of each persistent
        # tensor (a lopsided shard is a placement bug)
        active = self.kit.sharding_active_for(self.snapshot.capacity)
        active_shards = self.kit.shards if active else 1
        pod_shards = self.kit.pod_shards if active else 1
        metrics.solver_shard_count.set(float(active_shards))
        # per-axis split of the 2-D mesh (ISSUE 14): the flat
        # shard count can't distinguish 2x4 from 1x8
        metrics.solver_axis_shard_count.set(
            float(active_shards), labels={"axis": "nodes"})
        metrics.solver_axis_shard_count.set(
            float(pod_shards), labels={"axis": "pods"})
        if active_shards > 1 or pod_shards > 1:
            for kind, tree in (
                ("cluster_state", self.snapshot.state),
                ("candidate_cache",
                 cand["cache"] if cand else None),
            ):
                for (pi, ni), nbytes in (
                        insp.device_bytes_by_mesh_shard(
                            tree, self.kit.mesh).items()):
                    metrics.solver_device_bytes.set(
                        float(nbytes),
                        labels={"kind": kind,
                                "shard": f"p{pi}n{ni}"})
        if self.explain:
            # per-dim capacity slack: the headroom context for
            # the round's fit_<dim> rejection counts
            from koordinator_tpu.api.resources import ResourceDim

            free_sum, alloc_sum = self._slack_sums(
                self.snapshot.state)
            free_sum = np.asarray(free_sum)
            alloc_sum = np.asarray(alloc_sum)
            for dim in ResourceDim:
                total = float(alloc_sum[dim])
                metrics.capacity_slack.set(
                    (float(free_sum[dim]) / total) if total > 0
                    else 1.0,
                    labels={"dim": dim.name.lower()})

    def _recover_solve_failure(self) -> None:  # koordlint: guarded-by(self.lock)
        """The jitted solves DONATE the state buffers: an execution-time
        failure mid-round has already consumed them, and without
        recovery every later round would die on "Array has been
        deleted".  (Trace/compile errors — the common failure class —
        raise before any donation executes, so the buffers are still
        live and nothing is rebuilt.)  The conservative rebuild keeps
        the scheduler alive and never-overcommitting; a sync resync
        restores exact accounting."""
        if self.snapshot.state_buffers_deleted():
            self.snapshot.rebuild_conservative()
        self._cand_cache = None

    def _round_device(self) -> RoundHandle:  # koordlint: guarded-by(self.lock)
        """The DEVICE half of a round: gates, host prelude (reservation
        tick, nominations, quota revoke), PreEnqueue, BatchBuild, and
        the solve DISPATCH — no blocking on device results.  JAX's
        async dispatch returns immediately, so when the host half (or
        another tenant's) commit work runs next, this round's solve is
        already executing on the device.

        Donation contract (the double-buffered hand-off): the
        dispatched solve donates ``snapshot.state``'s buffers and the
        snapshot is re-pointed at the returned in-flight arrays before
        this method returns — the blessed swap.  The PRE-dispatch state
        must never be stashed (koordlint's donation-safety corpus seeds
        both sides of this idiom); reads of ``snapshot.state`` between
        the halves are safe and simply block until the solve lands.  A
        reserve or a release taken between the halves stays in the
        snapshot's pending delta and folds into the ADOPTED state at the
        host half, which reads the state back from the snapshot.

        Internally ``prepare`` (through BatchBuild) and ``dispatch``
        are separate steps so the tenancy front-end can gather every
        tenant's prepared batch and dispatch ONE tenant-axis batched
        program instead (tenancy._batched_dispatch)."""
        return self._round_dispatch(self._round_prepare())

    def _round_prepare(self) -> RoundHandle:  # koordlint: guarded-by(self.lock)
        """Gates + host prelude + PreEnqueue + BatchBuild (no solve)."""
        # set at round START — before any early return, including the
        # barrier gate, so a backlog building behind the barrier is visible.
        # Synthetic rsv:: reserve-pods are excluded (they are placement
        # vehicles, not user backlog — the auditor filters them the same way)
        metrics.pending_pods.set(float(sum(
            1 for name in self.pending
            if not name.startswith(RSV_POD_PREFIX))), labels=self._tl())
        handle = RoundHandle(result=SchedulingResult({}, {}, 0))
        if self.elector is not None and not self.elector.tick():
            # standby replica: keep syncing state, decide nothing — and
            # surface the standby (empty) result on the debug API instead
            # of a stale leader-era diagnosis
            self.last_result = handle.result
            handle.done = True
            return handle
        if self.barrier is not None and not self.barrier.check():
            # stale cache after restart: refuse to decide until the informer
            # replays past the barrier (sync_barrier.go semantics)
            handle.done = True
            return handle
        now = self.clock()
        handle.now = now
        # a round that got this far decided (or legitimately found
        # nothing to decide): it belongs in the flight recorder —
        # standby/barrier-gated rounds above do not
        self._round_recordable = True
        self._staleness_tick(now)
        if self.forecast_mode != "off" and self.forecast_plane is not None:
            # feed the forecast plane from the freshly-flushed usage
            # tensor (pre-dispatch: the state buffers are live) and
            # refresh predictions on the plane's own cadence
            self.snapshot.flush()
            self.forecast_plane.observe_state(self.snapshot.state)
            self.forecast_plane.maybe_refresh()
        result = handle.result
        self.last_result = result  # debug-API diagnosis surface
        if len(self.reservations):
            with self.monitor.phase("Reservations"):
                self.snapshot.flush()   # pinned-fit check reads device rows
                self._reservation_tick(now)
        if self.nominations:
            with self.monitor.phase("Nominated"):
                self.snapshot.flush()
                self._resolve_nominations(result)
        if self.overuse_revoke is not None and self.quota_tree is not None:
            with self.monitor.phase("QuotaRevoke"):
                # AFTER nominations (their released quota charges must not
                # trigger needless evictions) and BEFORE the solve (freed
                # headroom is visible to this round's admission); the
                # monitor must see a FRESH runtime — a stale/zeroed one
                # would flag healthy quotas
                self._refresh_quota_tree()
                self.overuse_revoke.revoke_once()
        with self.monitor.phase("PreEnqueue"):
            pods = self._active_pods()
        if not pods:
            # an all-suspended / all-parked queue still explains itself:
            # the held-out pods' explanations and the unschedulability
            # rollups must not depend on anything having SOLVED
            if self.explain:
                self._record_round_explanations(
                    [], result, [], set(), len(self.snapshot.node_index))
            handle.done = True
            return handle
        if self.auditor is not None:
            # one attempt per workload key per round — a gang is one
            # scheduling attempt, not len(members) attempts; synthetic
            # reserve-pods are not workloads
            keys = {pod.gang or pod.name for pod in pods
                    if not pod.name.startswith(RSV_POD_PREFIX)}
            # between two monitor phases: without a span of its own
            # this is time of the round that nothing names
            with timeline.RECORDER.section("host_other", "audit.attempts",
                                           self.tenant, n=len(keys)):
                self.auditor.record_attempts(keys)

        with self.monitor.phase("BatchBuild"):
            self.snapshot.flush()
            gangs, gang_index = self._build_gang_info(pods)
            quota, quota_index = self._build_quota()
            batch = self._build_batch(pods, gang_index, quota_index)
            batch = self._apply_topology_plans(batch, gang_index)
            # padding-waste fraction of the power-of-two pod bucketing:
            # device memory/FLOPs spent on rows no pod occupies
            metrics.solver_batch_padding_waste.set(
                1.0 - len(pods) / max(batch.capacity, 1))

        if (self.debug_service is not None
                and self.debug_service.dump_top_n_scores > 0):
            # debug-only extra solve: dump per-pod node scores.  BEFORE
            # the solve phase: the jitted solves donate the state
            # buffers, so pre-solve state is unreadable once they run.
            # The dump records scores against the PRE-ROUND accounting —
            # on reservation rounds that is now before the reservation
            # prepass adopts its bindings (previously the dump ran after
            # it), so it shows the state the round STARTED from
            from koordinator_tpu.ops.assignment import score_pods

            scores, _ = score_pods(self.snapshot.state, batch, self.config)
            self.debug_service.record_scores(
                pods, np.asarray(scores),
                [self.snapshot.node_name(r) or str(r)
                 for r in range(self.snapshot.state.capacity)],
            )
        handle.pods, handle.batch = pods, batch
        handle.gangs, handle.gang_index = gangs, gang_index
        handle.quota = quota
        return handle

    def _round_dispatch(self, handle: RoundHandle) -> RoundHandle:  # koordlint: guarded-by(self.lock)
        """Dispatch the prepared round's solve (async); see
        :meth:`_round_device` for the donation contract."""
        if handle.done:
            return handle
        pods, batch, result = handle.pods, handle.batch, handle.result
        gangs, gang_index = handle.gangs, handle.gang_index
        quota = handle.quota
        # dispatch wall is carried into the host half's single "Solve"
        # phase observation (monitor.phase carry_s) so the round still
        # produces exactly ONE Solve latency observation — the SLO
        # engine's per-observation bad fractions must not dilute
        dispatch_t0 = time.perf_counter()
        tl_t0 = timeline.RECORDER.open("round.dispatch")
        try:
            if self.faults is not None:
                # chaos seam: an injected solve delay lands in the
                # round's Solve scheduling_duration observation (via
                # carry_s) — the synthetic latency regression the SLO
                # engine's burn windows must catch (tests/test_slo_monitor)
                self.faults.on_solve()
            self._prepass_settled = None
            if len(self.reservations):
                batch, quota = self._reservation_prepass(
                    pods, batch, quota, result)
            solver = ("batch" if len(pods) >= self.batch_solver_threshold
                      else "greedy")
            self.last_solver = solver
            # forecast path (ISSUE 15): an active forecast round solves
            # with the headroom reserve charged into the accounting for
            # the duration of the solve.  The reserve re-shapes every
            # node's visible free capacity, so the incremental candidate
            # cache (scored against UNcharged state) and the quality
            # escalation latch (slack measured without the reserve) both
            # stand down — forecast rounds take the full charged path.
            forecast_reserve = self._forecast_reserve()
            handle.forecast_reserve = forecast_reserve
            # quality path (ISSUE 13): an escalated gangless round
            # solves with the LP-relaxation packing engine instead of
            # the greedy propose/accept rounds.  Gang rounds keep the
            # gang_assign path (all-or-nothing semantics live there;
            # quality mode reaches them through the topology planner
            # in _apply_topology_plans instead).
            use_quality = (
                forecast_reserve is None
                and not gang_index
                and (self.quality_mode == "lp"
                     or (self.quality_mode == "auto"
                         and self._quality_escalate)))
            # incremental fast path: a gangless batch round re-scores only
            # the delta against the persistent candidate cache; gang
            # rounds, hinted (dense-mask) rounds, the exact greedy
            # solver — and DEGRADED rounds, whose cache was built from
            # a stalled feed — keep the one-call full path
            use_inc = (not use_quality
                       and forecast_reserve is None
                       and solver == "batch" and self.incremental_solve
                       and not self.degraded
                       and not gang_index
                       and batch.selector_mask is not None)
            if use_quality:
                solver = "batch"   # the host half's rescue/commit path
                self.last_solver = solver
                self.last_solve_path = "quality_lp"
                metrics.incremental_solve_total.inc(
                    labels={"path": "quality_lp"})
                # pre-solve slack (async device sums, blocked on in the
                # host half): the quality_slack_recovered baseline.
                # Dispatched BEFORE the donating solve consumes the
                # state buffers.
                slack_before = self._slack_sums(self.snapshot.state)
                assignments, new_state, new_quota, qiters = (
                    self.kit.quality_solve(
                        self.snapshot.state, batch, self.config, quota))
                # the blessed swap (see the full-path branch below)
                self.snapshot.state = new_state
                # the LP solve re-packed everything: the candidate
                # cache's top-k is stale against the new accounting
                self._cand_cache = None
                handle.assignments = assignments
                handle.new_quota = new_quota
                handle.quality = {"iters": qiters,
                                  "slack_before": slack_before}
            elif use_inc:
                handle.inc = self._dispatch_batch_incremental(
                    pods, batch, quota)
                handle.assignments = handle.inc["a"]
                handle.new_quota = handle.inc["quota"]
            else:
                if solver == "batch":
                    self.last_solve_path = (
                        "forecast_full" if forecast_reserve is not None
                        else "full_gang" if gang_index
                        else "full_dense" if batch.selector_mask is None
                        else "degraded" if self.degraded
                        else "disabled")
                    metrics.incremental_solve_total.inc(labels={
                        "path": self.last_solve_path})
                if forecast_reserve is not None:
                    assignments, new_state, new_quota, grants, scanned = (
                        self.kit.forecast_solve(
                            self.snapshot.state, forecast_reserve, batch,
                            self.config, gangs, quota,
                            passes=self.gang_passes, solver=solver))
                else:
                    assignments, new_state, new_quota, grants, scanned = (
                        self.kit.solve(
                            self.snapshot.state, batch, self.config, gangs,
                            quota, passes=self.gang_passes, solver=solver))
                # the blessed swap: the jitted solve donated the old
                # state buffers; the snapshot re-points at the in-flight
                # result immediately so nothing can read the dead ones
                self.snapshot.state = new_state
                handle.assignments = assignments
                handle.new_quota = new_quota
                handle.grants = grants
                handle.scan_stats = scanned
        except Exception:
            self._recover_solve_failure()
            raise
        finally:
            self._solve_carry_s += time.perf_counter() - dispatch_t0
            timeline.RECORDER.close(tl_t0, "dispatch", self.tenant)
            if timeline.RECORDER.enabled and self._tl_device_t0 is None:
                # the async solve starts executing during this window:
                # its start doubles as the device-busy leading edge the
                # idle derivation pairs with the block edge
                self._tl_device_t0 = dispatch_t0
        # the prepass may have shrunk the batch and charged the quota
        handle.batch, handle.quota, handle.solver = batch, quota, solver
        # stamped here so the pipelined solve-half flight record carries
        # the admitted count (the host half re-stamps the same value)
        result.round_pods = len(pods)
        return handle

    # koordlint: guarded-by(self.lock)
    # koordlint: shape[a: P i32 rep, new_state: NxR i32 nodes]
    def round_adopt_batched(self, handle: RoundHandle, a, new_state,
                            new_quota, est_accum, cache) -> RoundHandle:
        """Adopt one tenant's slice of a TENANT-AXIS batched solve as
        this round's dispatched pass 1 (tenancy front-end;
        ``tenancy._batched_dispatch`` ran one ``vmap``-batched
        select+pass1 program over every tenant's stacked state).
        Mirrors the serial ``full_cold`` branch bookkeeping: the dirty
        set is consumed, the candidate cache re-warms from the batched
        selection (so the NEXT round goes incremental), and the finish
        context hands the pass-2 loop to :meth:`_round_host`."""
        snap = self.snapshot
        # the batched program re-selected every candidate: consume the
        # dirty set exactly like the serial full-selection path does
        snap.consume_candidate_dirty()
        self.last_solver = "batch"
        self.last_solve_path = "tenant_batched"
        metrics.incremental_solve_total.inc(
            labels={"path": "tenant_batched"})
        host = self._batch_host
        self._cand_cache = {
            "cache": cache,
            "row_of": host["row_of"],
            "specs": host["specs"],
            # the batched program is the single-device selection, vmapped
            "n": snap.capacity, "method": self.kit.method,
            "cfg": self.config,
        }
        # the blessed swap, batched form: the stacked program consumed a
        # COPY of the per-tenant states (stacking copies), so the old
        # buffers stay live until this re-point drops them
        snap.state = new_state
        handle.solver = "batch"
        handle.assignments = a
        handle.new_quota = new_quota
        handle.inc = {"a": a, "quota": new_quota,
                      "est_accum": est_accum, "batch": handle.batch}
        handle.result.round_pods = len(handle.pods)
        return handle

    # koordlint: guarded-by(self.lock)
    # koordlint: shape[a: P i32 rep, new_state: NxR i32 nodes]
    def round_adopt_quality_batched(self, handle: RoundHandle, a,
                                    new_state, new_quota, qiters,
                                    slack_before) -> RoundHandle:
        """Adopt one tenant's slice of the QUALITY tenant-axis solve
        (tenancy._dispatch_quality_axis_inner ran one vmapped
        lp_pack_assign over every escalated tenant's stacked state).
        Mirrors the standalone use_quality branch of _round_dispatch
        exactly: blessed swap, candidate-cache invalidation (the LP
        solve re-packed everything), and the handle.quality context
        _quality_round_finish consumes."""
        self.last_solver = "batch"
        self.last_solve_path = "quality_lp_batched"
        metrics.incremental_solve_total.inc(
            labels={"path": "quality_lp_batched"})
        # the blessed swap, batched form (see round_adopt_batched)
        self.snapshot.state = new_state
        self._cand_cache = None
        handle.solver = "batch"
        handle.assignments = a
        handle.new_quota = new_quota
        handle.quality = {"iters": qiters,
                          "slack_before": slack_before}
        handle.result.round_pods = len(handle.pods)
        return handle

    def _round_host(self, handle: RoundHandle) -> SchedulingResult:  # koordlint: guarded-by(self.lock)
        """The HOST half: block on the dispatched solve, run the exact
        rescue pass, then Reserve/Bind/Diagnose/PostFilter — the commit
        work round N+1's device solve overlaps under pipelined
        operation (tenancy front-end)."""
        if handle.done:
            self._journey_round_t0 = None   # gated round: no solve edge
            return handle.result
        pods, batch, result = handle.pods, handle.batch, handle.result
        gangs, quota, solver = handle.gangs, handle.quota, handle.solver
        now = handle.now
        assignments = handle.assignments
        new_quota = handle.new_quota
        try:
            with self.monitor.phase("Solve",
                                    carry_s=self._solve_carry_s):
                self._solve_carry_s = 0.0
                if handle.inc is not None:
                    assignments, new_state, new_quota, grants = (
                        self._finish_batch_incremental(handle.inc))
                else:
                    # the solve's in-flight state, read back from where
                    # dispatch swapped it in (see RoundHandle)
                    new_state = self.snapshot.state
                    grants = _RoundGrants.of(handle.grants)
                a = np.asarray(self._block_timed(assignments))
                valid = np.asarray(batch.valid)
                if handle.scan_stats is not None:
                    # the exact scan WAS the solve: counted, not a rescue
                    self._count_scan(int(valid.sum()), handle.scan_stats)
                leftover = valid & (a < 0)
                if solver == "batch" and bool(leftover[: len(pods)].any()):
                    # exact rescue pass over the leftovers: the batch engine's
                    # top-k/round approximation may fail pods a greedy scan
                    # would place, and a solver-approximation failure must
                    # never feed preemption, the gang WaitTime machine, or a
                    # persisted ScheduleFailed explanation. Rolled-back gangs
                    # come back whole; SURPLUS members of a gang already
                    # satisfied this round rescue as gangless pods (min_member
                    # is met — extras bind individually) so pre_enqueue/rollback
                    # inside the rescue solve can't strand them.
                    ga = np.asarray(batch.gang_id)
                    placed = np.bincount(
                        ga[(ga >= 0) & (a >= 0)], minlength=gangs.capacity)
                    satisfied = placed >= np.asarray(gangs.min_member)
                    gid = batch.gang_id
                    rescue_gid = jnp.where(
                        (gid >= 0) & jnp.asarray(satisfied)[jnp.maximum(gid, 0)],
                        -1, gid)
                    # compact the leftovers first: the exact greedy solve
                    # filters every row it is handed once and then steps over
                    # the live ones, so rescuing 50 pods must cost a 64-row
                    # filter, not one over the full 50k-row batch.
                    # ``leftover`` is the single source of truth for which rows
                    # rescue (compact keeps exactly those and marks the rest of
                    # the padded capacity invalid).
                    small, idx = batch.replace(gang_id=rescue_gid).compact(
                        leftover)
                    if handle.forecast_reserve is not None:
                        # a forecast round's rescue must see the SAME
                        # charged accounting as its main solve — an
                        # uncharged rescue would re-admit exactly the
                        # pods the reserve just filtered
                        r_small, new_state, new_quota, g_small, scanned = (
                            self.kit.forecast_solve(
                                new_state, handle.forecast_reserve, small,
                                self.config, gangs, new_quota,
                                passes=self.gang_passes, solver="greedy"))
                    else:
                        r_small, new_state, new_quota, g_small, scanned = (
                            self.kit.solve(
                                new_state, small, self.config, gangs,
                                new_quota,
                                passes=self.gang_passes, solver="greedy"))
                    self.snapshot.state = new_state
                    r_full = np.full(batch.capacity, -1, np.int32)
                    # the scan's step count rides the same program and
                    # the same block as its assignments: no extra wait
                    r_small, scanned = self._block_timed((r_small, scanned))
                    r_full[idx] = np.asarray(r_small)[: len(idx)]
                    self._rescue_scan = self._count_scan(len(idx), scanned)
                    grants = _RoundGrants.merged(
                        grants, g_small, idx, r_full[idx] >= 0,
                        batch.capacity)
                    assignments = jnp.where(
                        assignments >= 0, assignments, jnp.asarray(r_full))
                    a = np.asarray(assignments)
        except Exception:
            # execution-time donation failure: the block above is where
            # a dispatched-then-failed solve actually SURFACES, so the
            # conservative-rebuild recovery runs in both halves
            self._recover_solve_failure()
            raise
        result.round_pods = len(pods)
        # wall vs. device: the Solve phase's wall time is in the monitor;
        # this is the share spent blocked on jitted solve results
        metrics.solver_device_latency.observe(
            self._solve_device_s,
            labels={"path": (self.last_solve_path if solver == "batch"
                             else "greedy")},
            exemplar=({"trace_id": tracing.current_trace_id()}
                      if tracing.current_context() is not None else None))
        with self.monitor.phase("Reserve"):
            self.snapshot.adopt_state(new_state,
                                      changed_rows=np.unique(a[a >= 0]))
        if (handle.forecast_reserve is not None
                and self.forecast_plane is not None):
            # one small (R,) device reduction per FORECAST round (the
            # off mode never pays it): how much of the cluster the
            # admission reserve held back this round.  Tenant-labelled
            # like every scheduler gauge — per-tenant planes must not
            # overwrite each other's telemetry.
            metrics.forecast_admission_reserved_fraction.set(
                self.forecast_plane.reserve_fraction(
                    handle.forecast_reserve, self.snapshot.state),
                labels=self._tl())

        with self.monitor.phase("Bind"):
            placed_gangs: set[str] = set()
            binds: list[tuple[PodSpec, str]] = []
            bind_rows: list[int] = []
            for i, pod in enumerate(pods):
                node_row = int(a[i])
                if node_row >= 0:
                    node = self.snapshot.node_name(node_row)
                    if pod.name.startswith(RSV_POD_PREFIX):
                        self._commit_reserve_pod(pod, node, result, now)
                        continue
                    binds.append((pod, node))
                    bind_rows.append(i)
                    if pod.gang:
                        placed_gangs.add(pod.gang)
            self._commit_bind_batch(
                binds, result,
                None if grants is None else grants.selection[bind_rows])
            if grants is not None:
                self._count_device_outcomes(pods, a, grants)

        with self.monitor.phase("Diagnose"):
            admitted = None
            if quota is not None:
                from koordinator_tpu.quota.admission import quota_admission_mask

                # attribute against the POST-solve quota: a pod that lost
                # the headroom to this round's placements failed BECAUSE of
                # quota, even though pre-solve admission would have passed.
                # (Blame is applied per pod below only when nodes were
                # otherwise feasible — a pod that failed on capacity or
                # affinity keeps its real reason.)
                diag_quota = new_quota if new_quota is not None else quota
                admitted = np.asarray(quota_admission_mask(
                    diag_quota, batch.requests, batch.quota_id,
                    batch.non_preemptible
                ))
            fail_rows = [
                i for i, pod in enumerate(pods)
                if int(a[i]) < 0
                # a pod in assignments was bound by the reservation
                # pre-pass (batch row invalidated before the main solve)
                and pod.name not in result.assignments
            ]
            counts = feas = None
            row_pos: dict[int, int] = {}
            if self.explain and fail_rows:
                # ONE device reduction over the compacted failed rows
                # (ops/explain.explain_counts) instead of a host numpy
                # mask recompute per failed pod — O(F·NUM_REASONS) comes
                # back, the (F, N) masks never leave the device
                from koordinator_tpu.scheduler.diagnosis import (
                    diagnosis_from_counts,
                )

                fmask = np.zeros(batch.capacity, bool)
                fmask[fail_rows] = True
                small, idx = batch.compact(fmask)
                c_dev, f_dev = self._explain_counts(
                    self.snapshot.state, small, self.config)
                # plain block, NOT _block_timed: _solve_device_s feeds
                # the flight record's Solve-phase wall-vs-device split
                # (already observed by solver_device_latency), and
                # Diagnose-phase device time would skew both
                counts = np.asarray(jax.block_until_ready(c_dev))
                feas = np.asarray(f_dev)
                row_pos = {int(r): j for j, r in enumerate(idx)}
            total_nodes = len(self.snapshot.node_index)
            failed_gangs: set[str] = set()
            for i in fail_rows:
                pod = pods[i]
                if counts is not None:
                    # diagnosis_from_counts was imported when the kernel
                    # ran (counts is only non-None on that path)
                    j = row_pos[i]
                    diag = diagnosis_from_counts(
                        counts[j], int(feas[j]), total_nodes,
                        quota_admitted=True)
                else:
                    diag = explain_pod(
                        self.snapshot.state, batch, self.config, i,
                        quota_admitted=True,
                    )
                if (admitted is not None and not admitted[i]
                        and diag.feasible_nodes > 0):
                    # nodes were available but the quota (as of this
                    # round's placements) says no: quota is the cause
                    if diag.reason_counts is not None:
                        diag.reason_counts["quota"] = diag.feasible_nodes
                    diag = dataclasses.replace(
                        diag, quota_rejected=True, feasible_nodes=0)
                result.failures[pod.name] = diag
                if pod.gang:
                    failed_gangs.add(pod.gang)

            # gang WaitTime state machine (Permit timeout semantics)
            for name in failed_gangs - placed_gangs:
                gang = self.gangs.get(name)
                if gang is None:
                    continue
                if gang.first_failure is None:
                    gang.first_failure = now
                elif now - gang.first_failure > gang.wait_time_sec:
                    gang.rejected = True
            for name in placed_gangs:
                gang = self.gangs.get(name)
                if gang is not None:
                    gang.first_failure = None
            if self.explain:
                self._record_round_explanations(
                    pods, result, fail_rows, failed_gangs, total_nodes)

        if self.enable_preemption and result.failures:
            with self.monitor.phase("PostFilter"):
                self._run_preemption(pods, batch, result)

        if self.explanations is not None:
            # persist AFTER PostFilter so nominations land on the CR
            # (successful binds already cleared theirs in _commit_bind);
            # after the last phase, so under a span of its own
            with timeline.RECORDER.section("host_other", "diagnose.persist",
                                           self.tenant):
                self._persist_failures(pods, result)

        # every round in an ON mode runs the finish hook: "lp" gang
        # rounds must reset _last_quality_iters to 0 or their flight
        # records would carry the previous LP round's iteration count
        if handle.quality is not None or self.quality_mode != "off":
            self._quality_round_finish(handle, result)

        metrics.pending_pods.set(float(len(self.pending)),
                                 labels=self._tl())  # post-bind queue
        # round over: binds landed after this point (nomination
        # conversions, reservation draws outside a round) stamp their
        # own commit edge instead of inheriting this round's
        self._journey_round_t0 = None
        return result

    # koordlint: guarded-by(self.lock)
    def _persist_failures(self, pods, result: SchedulingResult) -> None:
        failed: list[tuple[str, str]] = []
        for pod in pods:
            if pod.name.startswith(RSV_POD_PREFIX):
                # an unplaced reservation retries next round; it is not
                # a user pod and must not persist ScheduleFailed CRs
                continue
            diag = result.failures.get(pod.name)
            if diag is not None:
                # one diagnose.explain run: the store's share
                tl_t0 = time.perf_counter()
                self.explanations.record(pod.name, diag)
                timeline.RECORDER.add(
                    tl_t0, time.perf_counter(), "host_other",
                    "diagnose.explain", self.tenant)
                if self.auditor is not None:
                    failed.append((pod.gang or pod.name, diag.message()))
        if failed:
            self.auditor.record_many("ScheduleFailed", failed)

    # -- solve-quality mode (ISSUE 13) --------------------------------------

    def arm_quality_escalation(self) -> None:
        """Arm the auto-mode escalation latch by hand — a warmup aid.

        A harness (tools/loadgen) can force its warm round onto the LP
        path so the quality program's one-time jit compile lands BEFORE
        any latency-SLO or trend window opens; without this, auto mode
        pays the compile on the first round that escalates mid-run.
        No-op when ``quality_mode == "off"``; the latch re-evaluates
        from real slack at the end of every round, so arming never
        sticks past the next round.
        """
        if self.quality_mode != "off":
            with self.lock:
                self._quality_escalate = True

    def _quality_round_finish(self, handle: RoundHandle, result) -> None:  # koordlint: guarded-by(self.lock)
        """Quality-round accounting + the auto-mode escalation latch.

        Runs at the END of the host half so the slack sums see the
        round's final accounting (rescue pass included) and the outcome
        label sees the diagnosed failures.  One cheap jitted (R,)
        reduction per round — the same kernel the explain rollup uses.
        """
        from koordinator_tpu.api.resources import ResourceDim

        free_sum, alloc_sum = self._slack_sums(self.snapshot.state)
        free_sum = np.asarray(free_sum)
        alloc_sum = np.asarray(alloc_sum)
        # min over provisioned dims: escalation means EVERY dimension
        # still has headroom worth winning back (a cluster out of CPU
        # but swimming in memory has nothing a better packing recovers)
        slack_min = min(
            (float(free_sum[d]) / float(alloc_sum[d])
             for d in ResourceDim if float(alloc_sum[d]) > 0),
            default=0.0)
        self._quality_escalate = slack_min > self.quality_slack_threshold
        if handle.quality is None:
            self._last_quality_iters = 0
            return
        iters = int(np.asarray(self._block_timed(
            handle.quality["iters"])))
        self._last_quality_iters = iters
        metrics.quality_iterations.observe(float(iters),
                                           labels=self._tl())
        free_b, alloc_b = (np.asarray(x)
                           for x in handle.quality["slack_before"])
        for dim in ResourceDim:
            total = float(alloc_b[dim])
            recovered = ((float(free_b[dim]) - float(free_sum[dim]))
                         / total if total > 0 else 0.0)
            metrics.quality_slack_recovered.set(
                max(recovered, 0.0), labels={"dim": dim.name.lower()})
        outcome = "partial" if result.failures else "complete"
        metrics.quality_rounds.inc(
            labels={"mode": self.quality_mode, "outcome": outcome})

    # -- incremental delta-driven solve -------------------------------------

    def _count_scan(self, rows: int, stats) -> tuple[int, int]:
        """(rows, steps) of one exact-scan solve whose results the caller
        already holds: ``rows`` pods were handed to it, ``stats``
        (``ops/assignment.ScanStats``) says how many loop steps it took
        over them (summed over gang passes: a row live at the entry of two
        passes counts two steps).  Adds both outcomes to
        ``solver_greedy_scan_rows_total``."""
        steps = int(np.asarray(stats.steps))
        metrics.greedy_scan_rows.inc(steps, labels={"outcome": "stepped"})
        metrics.greedy_scan_rows.inc(max(rows - steps, 0),
                                     labels={"outcome": "pruned"})
        return rows, steps

    def _block_timed(self, value):  # koordlint: guarded-by(self.lock)
        """Block on a jitted solve's result, accumulating the wait into
        the round's device-time share (``_solve_device_s``).  The
        dispatch itself returns immediately (async execution), so time
        spent HERE is device compute + transfer — the wall-vs-device
        split the flight recorder and round span report."""
        t0 = time.perf_counter()
        value = jax.block_until_ready(value)
        t1 = time.perf_counter()
        self._solve_device_s += t1 - t0
        if timeline.RECORDER.enabled:
            # merge=False: device_block equals pipeline_host_wait_fraction
            # by construction, and idle is derived from exact busy edges
            timeline.RECORDER.add(t0, t1, "device_block",
                                  "block_until_ready", self.tenant,
                                  merge=False)
            # device-busy span: the dispatch edge (when this round
            # dispatched async work) to this block edge.  A block with
            # no tracked dispatch (rescue pass) contributes just its
            # own wait — an under-estimate of busy, never of idle.
            busy_t0 = getattr(self, "_tl_device_t0", None)
            timeline.RECORDER.add(busy_t0 if busy_t0 is not None else t0,
                                  t1, timeline.DEVICE_BUSY,
                                  "solve", self.tenant, merge=False)
            self._tl_device_t0 = None
        return value

    def sharding_report(self) -> dict:
        """The /debug/slo "sharded solve" section: active mesh shape,
        per-device bytes of the persistent solver tensors, and the
        recompile counters per (fn, shape) bucket — shape buckets carry
        an ``@<n>shard`` suffix while the mesh is active, so a
        per-mesh-shape compile regression reads straight off this
        document (and off ``solver_recompiles_total{shape}``)."""
        from koordinator_tpu.ops import introspection as insp
        from koordinator_tpu.parallel import mesh as pmesh

        cand = self._cand_cache
        mesh = self.kit.mesh

        def _by_shard(tree):
            # keyed by (pod_shard, node_shard) mesh coordinate when the
            # mesh exists (ISSUE 14), flat device id otherwise
            if mesh is not None:
                return {f"p{pi}n{ni}": b for (pi, ni), b in
                        insp.device_bytes_by_mesh_shard(
                            tree, mesh).items()}
            return {str(d): b for d, b in
                    insp.device_bytes_by_shard(tree).items()}

        return {
            "solver_shard_count": self.kit.shards,
            "active": self.kit.sharding_active_for(self.snapshot.capacity),
            "mesh": pmesh.mesh_axes(mesh),
            "pod_shard_count": self.kit.pod_shards,
            "shard_min_nodes": self.kit.shard_min_nodes,
            "device_bytes_by_shard": {
                # a scrape holds no lock, so it reads the tensors as they
                # stand: ``snapshot.state`` would fold, which is a write
                "cluster_state": _by_shard(self.snapshot.resident_state),
                "candidate_cache": _by_shard(
                    cand["cache"] if cand else None),
            },
            "recompiles_by_shape": {
                f"{lbl.get('fn', '?')}[{lbl.get('shape', '?')}]": int(v)
                for lbl, v in metrics.solver_recompiles.items()},
        }

    def _solve_batch_incremental(self, pods, batch: PodBatch, quota):  # koordlint: guarded-by(self.lock)
        """One-call form of the incremental solve (dispatch + finish):
        kept for callers outside the round pipeline.  Returns
        (assignments, new_state, new_quota) like gang_assign."""
        return self._finish_batch_incremental(
            self._dispatch_batch_incremental(pods, batch, quota))[:3]

    def _dispatch_batch_incremental(self, pods, batch: PodBatch, quota) -> dict:  # koordlint: guarded-by(self.lock)
        """The no-gang batch solve with the persistent device-resident
        candidate cache (ops/batch_assign incremental section) — the
        DEVICE half: candidate refresh/selection and the pass-1 solve
        are dispatched (async) and returned as a finish context for
        :meth:`_finish_batch_incremental`; nothing heavy is blocked on
        here (the (P,) ``touch`` readback for dirty-pod mapping is the
        one small sync).

        Steady state: the round re-scores only dirty rows — pods newly
        arrived/re-specced or whose cached candidates touch a dirty node —
        against a dirty-node column mask accumulated by the snapshot from
        the deltas applied under this scheduler's round lock, then merges
        them into the cached (P, k) tensor.  When the dirty fraction
        crosses ``incremental_dirty_threshold`` (or no valid cache
        exists) the full selection runs instead and re-warms the cache.
        Either way the propose/accept passes afterwards mirror
        gang_assign's gangless pass loop bit for bit, so flipping paths
        never changes acceptance decisions — staleness in the cache can
        only cost candidate recall, and acceptance re-checks fit and
        quota exactly.
        """
        from koordinator_tpu.ops import batch_assign as ba

        snap = self.snapshot
        n = snap.capacity
        # which selection (sharded, or a single-device method) the kit
        # runs for these shapes: a cache another selection built is cold
        selection = self.kit.selection(n, batch,
                                       devices=self._has_devices())
        meta = self._cand_cache
        cache_ok = (
            meta is not None
            and meta["n"] == n
            and meta["method"] == selection
            # identity via the OBJECT, not id(): a freed config's address
            # can be reused by its replacement (CPython free lists)
            and meta["cfg"] is self.config
        )
        # consumed exactly once per cache rebuild/refresh — both branches
        # below leave a cache that reflects post-consume state
        dirty_rows = [r for r in snap.consume_candidate_dirty() if r < n]
        settled, self._prepass_settled = self._prepass_settled, None

        path = "full_cold"
        cache = None
        if cache_ok:
            node_frac = len(dirty_rows) / max(len(snap.node_index), 1)
            row_of, specs = meta["row_of"], meta["specs"]
            map_rows = np.zeros(batch.capacity, np.int32)
            map_ok = np.zeros(batch.capacity, bool)
            changed = np.zeros(batch.capacity, bool)
            for i, pod in enumerate(pods):
                if settled is not None and settled[i]:
                    # the reservation pre-pass settled it: it takes no
                    # part in this solve, so it is no dirty row of it
                    continue
                j = row_of.get(pod.name)
                if j is not None and specs.get(pod.name) is pod:
                    map_rows[i] = j
                    map_ok[i] = True
                else:
                    changed[i] = True
            dirty_np = np.zeros(n, bool)
            dirty_np[dirty_rows] = True
            dpad = _bucket(max(len(dirty_rows), 1), minimum=64)
            drows = np.zeros(dpad, np.int32)
            drows[: len(dirty_rows)] = dirty_rows
            dvalid = np.zeros(dpad, bool)
            dvalid[: len(dirty_rows)] = True
            aligned, touch = self._align_cands(
                meta["cache"], jnp.asarray(map_rows), jnp.asarray(map_ok),
                jnp.asarray(dirty_np))
            dirty_pods = changed | np.asarray(touch)
            pod_frac = float(dirty_pods.sum()) / max(len(pods), 1)
            metrics.incremental_dirty_fraction.set(
                node_frac, labels={"kind": "nodes"})
            metrics.incremental_dirty_fraction.set(
                pod_frac, labels={"kind": "pods"})
            metrics.incremental_dirty_pods.set(float(dirty_pods.sum()))
            self._last_dirty_node_frac = node_frac
            self._last_dirty_pod_frac = pod_frac
            if max(node_frac, pod_frac) <= self.incremental_dirty_threshold:
                path = "incremental"
                cand_key, cache = self.kit.refresh_cands(
                    snap.state, batch, self.config, aligned,
                    jnp.asarray(drows), jnp.asarray(dvalid))
                if dirty_pods.any():
                    small, idx = batch.compact(dirty_pods)
                    sk, sn, ss = self.kit.select_scored(
                        snap.state, small, self.config)
                    rows_pad = np.full(small.capacity, batch.capacity,
                                       np.int32)
                    rows_pad[: len(idx)] = idx
                    cache = self._scatter_cands(
                        cache, jnp.asarray(rows_pad), sk, sn, ss)
            else:
                path = "full_fallback"
        if cache is None:
            ck, cn, cs = self.kit.select_scored(
                snap.state, batch, self.config)
            cache = ba.CandidateCache(ck, cn, cs)
        metrics.incremental_solve_total.inc(labels={"path": path})
        # the batch build already computed this round's name→row / spec
        # maps for its own row reuse — share them instead of a third O(P)
        # walk (the driver only runs on non-hinted batches, which always
        # populate _batch_host)
        host = self._batch_host
        row_of = host["row_of"]
        if settled is not None:
            # no candidates were kept for a settled row: were it to come
            # back (a reserve-pod that waits), it must read as new
            row_of = {pod.name: i for i, pod in enumerate(pods)
                      if not settled[i]}
        self._cand_cache = {
            "cache": cache,
            "row_of": row_of,
            "specs": host["specs"],
            "n": n, "method": selection, "cfg": self.config,
        }
        self.last_solve_path = path

        # gangless gang_assign pass loop, pass 1: over the
        # cached/refreshed candidates.  The pass donates the state it
        # consumes; re-pointing snapshot.state at the returned state
        # keeps the snapshot on LIVE buffers (trace/compile errors —
        # the realistic failure class — raise before any donation
        # executes; an execution-time failure mid-chain is
        # unrecoverable without a sync resync either way).  On any
        # failure the cache is dropped so the next round re-warms
        # instead of trusting un-bookkept state.
        try:
            a, state, quota, est_accum, grants = self.kit.pass1(
                snap.state, batch, quota, cache.cand_key, cache.cand_node,
                self.config)
            snap.state = state
        except Exception:
            self._cand_cache = None
            raise
        return {"a": a, "quota": quota, "est_accum": est_accum,
                "batch": batch, "grants": grants}

    def _finish_batch_incremental(self, ctx: dict):  # koordlint: guarded-by(self.lock)
        """HOST half of the incremental solve: block on pass 1, then
        run the later passes full-selecting over the COMPACTED leftovers
        (small × N, not P × N) against the est-usage-augmented state —
        identical decisions to the one-call form, dispatch point aside."""
        snap = self.snapshot
        batch = ctx["batch"]
        # pass 1's in-flight state, as dispatch left it in the snapshot,
        # with whatever was reserved or released since folded in
        state, quota, est_accum = snap.state, ctx["quota"], ctx["est_accum"]
        try:
            # a copy: np.asarray of a device array is a read-only view,
            # and a later pass writes what it placed into it
            a_np = np.array(self._block_timed(ctx["a"]))
            grants = _RoundGrants.of(ctx.get("grants"))
            for _ in range(1, self.gang_passes):
                leftover = np.asarray(batch.valid) & (a_np < 0)
                if not leftover.any():
                    break
                small, idx = batch.compact(leftover)
                a2, state, quota, est_accum, g2 = self.kit.pass2(
                    state, est_accum, small, quota, self.config)
                snap.state = state
                a2_np = np.asarray(self._block_timed(a2))[: len(idx)]
                placed = a2_np >= 0
                grants = _RoundGrants.merged(grants, g2, idx, placed,
                                             batch.capacity)
                if not placed.any():
                    break
                a_np[idx[placed]] = a2_np[placed]
        except Exception:
            self._cand_cache = None
            raise
        return jnp.asarray(a_np), state, quota, grants

    # -- placement explainability (ISSUE 6) ---------------------------------

    # koordlint: guarded-by(self.lock)
    def _record_round_explanations(
        self, pods, result: SchedulingResult, fail_rows: list[int],
        failed_gangs: set[str], total_nodes: int,
    ) -> None:
        """Assemble :class:`PlacementExplanation` records for every pod
        the round left unplaced — solve failures (from the device
        kernel's counts now on the diagnoses), degraded-suspended pods,
        and rejected-gang parkees — then publish the cluster rollups:
        ``unschedulable_pods{reason}`` (top reason per pod),
        ``filter_reject_fraction{reason}``, and the flight recorder's
        ``top_unschedulable`` summary."""
        from koordinator_tpu.ops import explain as ex
        from koordinator_tpu.scheduler.explanation import PlacementExplanation

        explanations: list[PlacementExplanation] = []
        for i in fail_rows:
            pod = pods[i]
            if pod.name.startswith(RSV_POD_PREFIX):
                # reservation vehicles retry next round; they are not
                # user workloads (mirrors the auditor/tracing exclusion)
                continue
            diag = result.failures.get(pod.name)
            if diag is None:
                continue
            # node_invalid counts PADDED state rows too (padding and
            # removed nodes are the same validity bit) — it would swamp
            # the real reasons, so the served explanation partitions
            # only the LIVE nodes: feasible + sum(reasons) == total
            reasons = {name: count
                       for name, count in (diag.reason_counts or {}).items()
                       if count > 0 and name != "node_invalid"}
            feasible = diag.feasible_nodes
            if (pod.gang is not None and pod.gang in failed_gangs
                    and feasible > 0):
                # nodes were individually feasible; the gang barrier
                # (minMember/rollback) held the placement back
                reasons["gang_barrier"] = feasible
                feasible = 0
            explanations.append(PlacementExplanation(
                pod=pod.name, round=self.round_seq,
                total_nodes=total_nodes, feasible_nodes=feasible,
                reasons=reasons, trace_id=self.pod_trace_id(pod.name),
                quota=pod.quota if diag.quota_rejected else None,
                gang=pod.gang))
        for name in self._last_suspended_names:
            explanations.append(PlacementExplanation(
                pod=name, round=self.round_seq, total_nodes=total_nodes,
                feasible_nodes=0,
                reasons={"degraded_suspended": total_nodes},
                trace_id=self.pod_trace_id(name),
                gang=getattr(self.pending.get(name), "gang", None)))
        for name in self._last_gang_rejected_names:
            explanations.append(PlacementExplanation(
                pod=name, round=self.round_seq, total_nodes=total_nodes,
                feasible_nodes=0, reasons={"gang_barrier": total_nodes},
                trace_id=self.pod_trace_id(name),
                gang=getattr(self.pending.get(name), "gang", None)))

        top: dict[str, int] = {}
        reason_sums: dict[str, int] = {}
        for exp in explanations:
            self.explain_ring.record(exp)
            reason = exp.top_reason()
            if reason is not None:
                top[reason] = top.get(reason, 0) + 1
            for name, count in exp.reasons.items():
                reason_sums[name] = reason_sums.get(name, 0) + count
        self._last_unschedulable_top = dict(
            sorted(top.items(), key=lambda kv: (-kv[1], kv[0])))
        # republish EVERY reason each round so a cleared reason reads 0
        # instead of its last nonzero value lingering on the dashboard
        for name in ex.REASON_NAMES:
            metrics.unschedulable_pods.set(
                float(top.get(name, 0)), labels={"reason": name})
        if explanations and total_nodes:
            denom = len(explanations) * total_nodes
            for name, total in reason_sums.items():
                metrics.filter_reject_fraction.observe(
                    total / denom, labels={"reason": name})

    def pod_explanation(self, name: str):
        """Latest retained :class:`PlacementExplanation` for a pod."""
        return self.explain_ring.get(name)

    def explain_candidates(self, name: str, k: int = 5) -> list[dict] | None:
        """Per-term score decomposition (ops/explain.decompose_scores) of
        a pod's top-k candidate nodes — or, for a bound pod, its winning
        node — against CURRENT state.  On-demand debug surface: one
        small (1, N) score pass, no hot-path cost.  None = unknown pod.
        """
        from koordinator_tpu.ops import explain as ex
        from koordinator_tpu.ops.assignment import score_pods

        with self.lock:
            pod = self.pending.get(name)
            bound = self.bound.get(name)
            if pod is None and bound is None:
                return None
            self.snapshot.flush()
            state = self.snapshot.state

            def decompose(batch, node_rows: np.ndarray) -> list[dict]:
                cand = jnp.asarray(node_rows[None, :].astype(np.int32))
                terms = {t: np.asarray(v)[0]
                         for t, v in ex.decompose_scores(
                             state, batch, self.config, cand).items()}
                return [
                    {"node": self.snapshot.node_name(int(r)) or str(int(r)),
                     "score": int(terms["total"][j]),
                     "terms": {t: int(v[j]) for t, v in terms.items()
                               if t != "total"}}
                    for j, r in enumerate(node_rows)
                ]

            if pod is not None:
                batch = PodBatch.build(
                    pod.requests[None].astype(np.int32),
                    priority=np.array([pod.priority], np.int32),
                    feasible=self.snapshot.feasibility_row(pod)[None],
                    node_capacity=self.snapshot.capacity, capacity=16,
                )
                scores, feasible = score_pods(state, batch, self.config)
                row = np.asarray(scores[0])
                masked = np.where(np.asarray(feasible[0]), row, -1)
                order = np.argsort(-masked, kind="stable")[:max(k, 1)]
                order = order[masked[order] >= 0]
                if order.size == 0:
                    return []
                return decompose(batch, order)
            row_idx = self.snapshot.node_index.get(bound.node)
            if row_idx is None:
                return []
            batch = PodBatch.build(
                bound.requests[None].astype(np.int32),
                node_capacity=self.snapshot.capacity, capacity=16)
            out = decompose(batch, np.array([row_idx], np.int32))
            out[0]["winner"] = True
            return out

    # koordlint: guarded-by(self.lock)
    def _commit_bind(
        self, pod: PodSpec, node: str, result: SchedulingResult,
        charge_quota: bool = True,
        reservation: str | None = None,
        rsv_drawn: np.ndarray | None = None,
        rsv_generation: int = 0,
    ) -> None:
        """Shared bind bookkeeping: assignments, bound registry, quota used.

        ``charge_quota=False`` converts a nomination whose quota charge is
        already on the tree (``_nomination_assume``)."""
        commit_t0 = time.perf_counter()
        # the batch commit's bind.* spans, one pod at a time: each stamp
        # closes the step above it
        tl, tenant = timeline.RECORDER, self.tenant
        result.assignments[pod.name] = node
        if self.pending.pop(pod.name, None) is not None:
            self._pending_rev += 1
        self.nominations.pop(pod.name, None)
        self._nomination_gen.pop(pod.name, None)
        self.bound[pod.name] = BoundPod(
            name=pod.name, node=node, requests=pod.requests,
            priority=pod.priority, quota=pod.quota,
            non_preemptible=pod.non_preemptible,
            labels=pod.labels, gang=pod.gang, qos=pod.qos, owner=pod.owner,
            reservation=reservation, rsv_drawn=rsv_drawn,
            rsv_generation=rsv_generation,
            node_generation=self.snapshot.node_generation.get(node, 0),
        )
        t_registry = time.perf_counter()
        tl.add(commit_t0, t_registry, "bind_commit", "bind.registry", tenant)
        if charge_quota:
            self._charge_quota_used(pod, sign=1)
        t_quota = time.perf_counter()
        tl.add(t_registry, t_quota, "bind_commit", "bind.quota", tenant)
        if self._grant_devices([(pod, node)], None, result):
            return   # no device grant: unreserved, pending again
        self._allocate_fine_grained(pod, node)
        # bind marker in the POD's trace (parented to its enqueue span,
        # linked to the round's trace by attribute), and the trace
        # annotation the deployment shell carries onto the bound pod
        # object — the koordlet's reconciler joins the trace from it,
        # the way the reference propagates through patched annotations.
        # AFTER _allocate_fine_grained: that call replaces the pod's
        # resource_status entry wholesale.
        ctx = self.pod_traces.pop(pod.name, None)
        if ctx is not None:
            sp = tracing.TRACER.start_span(
                "scheduler.bind", service="scheduler", parent=ctx,
                attributes={"pod": pod.name, "node": node,
                            "round": self.round_seq,
                            "round_trace_id": tracing.current_trace_id()})
            sp.end()
            self.resource_status.setdefault(pod.name, {})[
                tracing.TRACE_ANNOTATION] = sp.context().to_annotation()
        t_surfaces = time.perf_counter()
        tl.add(t_quota, t_surfaces, "bind_commit", "bind.surfaces", tenant)
        if self.bind_fn is not None:
            self.bind_fn(pod.name, node)
        t_emit = time.perf_counter()
        tl.add(t_surfaces, t_emit, "bind_commit", "bind.emit", tenant)
        # success side of ScheduleExplanation/auditor lifecycle lives here so
        # nominated binds (Nominated phase, before _active_pods) clear their
        # stale failure explanations too
        if self.explanations is not None:
            self.explanations.delete(pod.name)
        if self.auditor is not None:
            self.auditor.record(pod.gang or pod.name, "ScheduleSuccess", node)
        t_explain = time.perf_counter()
        tl.add(t_emit, t_explain, "bind_commit", "bind.explain", tenant)
        if journey.LEDGER.enabled:
            round_t0 = self._journey_round_t0
            journey.LEDGER.record_bind_batch(
                self.tenant, (pod,),
                round_start_perf=(round_t0 if round_t0 is not None
                                  else commit_t0),
                commit_perf=commit_t0)
            tl.add(t_explain, time.perf_counter(), "bind_commit",
                   "bind.journey", tenant)

    # koordlint: guarded-by(self.lock)
    def _commit_bind_batch(self, binds: list[tuple[PodSpec, str]],
                           result: SchedulingResult,
                           selections: np.ndarray | None = None) -> None:
        """One batched commit for a round's whole bind set (ISSUE 19).

        Sequential ``_commit_bind`` re-walks the quota tree and bumps
        ``q.used`` once per pod — at 1k binds/round that is 1k int64
        adds plus 1k dict probes of pure host time inside the round's
        critical section.  Here the per-pod registry bookkeeping stays a
        (cheap) loop, but quota recharge is grouped: one
        ``np.sum(stack)`` per (quota, non_preemptible) group and ONE
        ``q.used`` update per touched quota node.  Integer adds commute
        and int64 never rounds, so the grouped totals are bit-identical
        to the sequential charges (the reserve_batch precedent).  Per-
        pod surfaces — ``resource_status``, trace stamping, fine-grained
        allocation — are preserved exactly, in bind order; the
        explanation store and the auditor take the bind set as one
        batched call each, in that order too.  ``bind_batch_fn`` (when
        set) receives the whole set once: the seam for one deltasync
        emission per round instead of one frame per pod.

        ``selections``: the (len(binds), D) device grants the solve made
        for these binds (an all-False row: none), None when it carried
        no device stage; see :meth:`_grant_devices`."""
        if not binds:
            return
        commit_t0 = time.perf_counter()
        tl, tenant = timeline.RECORDER, self.tenant
        # phase 1: registry bookkeeping (assignments / pending /
        # nominations / bound), in order — later same-name entries win
        # exactly as they would sequentially
        with tl.section("bind_commit", "bind.registry", tenant):
            for pod, node in binds:
                result.assignments[pod.name] = node
                if self.pending.pop(pod.name, None) is not None:
                    self._pending_rev += 1
                self.nominations.pop(pod.name, None)
                self._nomination_gen.pop(pod.name, None)
                self.bound[pod.name] = BoundPod(
                    name=pod.name, node=node, requests=pod.requests,
                    priority=pod.priority, quota=pod.quota,
                    non_preemptible=pod.non_preemptible,
                    labels=pod.labels, gang=pod.gang, qos=pod.qos,
                    owner=pod.owner,
                    node_generation=self.snapshot.node_generation.get(
                        node, 0),
                )
        # phase 2: grouped quota recharge — one used-vector update per
        # touched quota node instead of one per pod
        if self.quota_tree is not None:
            with tl.section("bind_commit", "bind.quota", tenant):
                groups: dict[tuple[str, bool], list[np.ndarray]] = {}
                for pod, _node in binds:
                    if pod.quota and pod.quota in self.quota_tree.nodes:
                        groups.setdefault(
                            (pod.quota, bool(pod.non_preemptible)), []
                        ).append(pod.requests)
                for (quota, non_preemptible), reqs in groups.items():
                    q = self.quota_tree.nodes[quota]
                    total = np.sum(np.stack(reqs).astype(np.int64), axis=0)
                    q.used = q.used + total
                    if non_preemptible:
                        q.non_preemptible_used = (
                            q.non_preemptible_used + total)
        # phase 2b: DeviceShare Reserve for the round, in one step; a pod
        # no grant could be made for is unreserved and leaves ``binds``
        refused = self._grant_devices(binds, selections, result)
        if refused:
            binds = [b for i, b in enumerate(binds) if i not in refused]
            if not binds:
                return
        # phase 3: per-pod surfaces, in bind order (fine-grained state
        # mutates per node+pod; trace stamping must follow it because
        # _allocate_fine_grained replaces resource_status wholesale)
        with tl.section("bind_commit", "bind.surfaces", tenant):
            for pod, node in binds:
                self._allocate_fine_grained(pod, node)
                ctx = self.pod_traces.pop(pod.name, None)
                if ctx is not None:
                    sp = tracing.TRACER.start_span(
                        "scheduler.bind", service="scheduler", parent=ctx,
                        attributes={"pod": pod.name, "node": node,
                                    "round": self.round_seq,
                                    "round_trace_id":
                                        tracing.current_trace_id()})
                    sp.end()
                    self.resource_status.setdefault(pod.name, {})[
                        tracing.TRACE_ANNOTATION] = (
                            sp.context().to_annotation())
            # the store and the auditor share no state with the loop
            # above, so each takes the round's binds as one batch
            if self.explanations is not None:
                # the store's share of the commit, n = deletes
                with tl.section("bind_commit", "bind.explain", tenant,
                                n=len(binds)):
                    self.explanations.delete_many(
                        pod.name for pod, _node in binds)
            if self.auditor is not None:
                self.auditor.record_many(
                    "ScheduleSuccess",
                    ((pod.gang or pod.name, node) for pod, node in binds))
        with tl.section("bind_commit", "bind.emit", tenant):
            if self.bind_batch_fn is not None:
                self.bind_batch_fn(
                    [(pod.name, node) for pod, node in binds])
            elif self.bind_fn is not None:
                for pod, node in binds:
                    self.bind_fn(pod.name, node)
        # journey ledger (ISSUE 20): one vectorized pass records the whole
        # round's e2e + stage latencies.  Pure observation — runs after
        # every decision and quota charge above is already committed, so
        # KOORD_JOURNEY=0 is bit-identical on scheduling outcomes.
        if journey.LEDGER.enabled:
            with tl.section("bind_commit", "bind.journey", tenant):
                round_t0 = self._journey_round_t0
                journey.LEDGER.record_bind_batch(
                    self.tenant, [pod for pod, _node in binds],
                    round_start_perf=(round_t0 if round_t0 is not None
                                      else commit_t0),
                    commit_perf=commit_t0)

    # koordlint: guarded-by(self.lock)
    def _grant_devices(self, binds: list[tuple[PodSpec, str]],
                       selections: np.ndarray | None,
                       result: SchedulingResult) -> set[int]:
        """DeviceShare Reserve for a set of just-registered binds, and
        upstream's rule with it: a pod that asks for a device is bound
        only together with a grant that satisfies it.

        Where the solve carried the device stage its grant arrives in
        ``selections`` and all of them are written into the books in ONE
        call (``DeviceManager.record_grants``): no device op, no per-pod
        manager call.  A bind from a path without the stage (the sharded
        twins, the LP and tenant-axis solves, the reservation pre-pass,
        a nomination) takes its grant here from the host books by the
        same rule.  When none fits, the bind is undone (Unreserve): node
        accounting and quota released, the pod pending again with a
        diagnosis that names the device.  Returns the refused binds'
        indices."""
        from koordinator_tpu.api.resources import ResourceDim

        manager = self.device_manager
        if manager is None or manager.solve_table() is None:
            return set()   # no node has a device inventory: legacy rows
        wanting = [i for i, (pod, _node) in enumerate(binds)
                   if _wants_device(pod)]
        if not wanting:
            return set()
        refused: set[int] = set()
        t0 = timeline.RECORDER.open("bind.devices")
        # the solve's grants first, all in one call: a grant taken from
        # the books below must see them
        solved: list[tuple[str, str, list[int], int, int]] = []
        unsolved: list[int] = []
        for i in wanting:
            pod, node = binds[i]
            minors = (np.flatnonzero(selections[i]).tolist()
                      if selections is not None else [])
            if minors:
                _, per_core, per_mem = split_request(
                    int(pod.requests[ResourceDim.GPU]),
                    int(pod.requests[ResourceDim.GPU_MEMORY]))
                solved.append((pod.name, node, minors, per_core, per_mem))
            else:
                unsolved.append(i)
        if solved:
            manager.record_grants(solved)
        for i in unsolved:
            pod, node = binds[i]
            if manager.allocate(
                    SOLVE_DEVICE_TYPE, node, pod.name,
                    int(pod.requests[ResourceDim.GPU]),
                    int(pod.requests[ResourceDim.GPU_MEMORY])) is None:
                refused.add(i)
        for i in refused:
            self._unbind_for_devices(*binds[i], result)
        timeline.RECORDER.close(t0, "bind_commit", self.tenant,
                                n=len(wanting) - len(refused))
        for i in wanting:
            if i in refused:
                continue
            pod, node = binds[i]
            status = self.resource_status.setdefault(pod.name, {})
            status["device-allocated"] = (
                manager.device_allocated_annotation(node, pod.name))
            self._adapt_device_plugin(pod, node, status)
        metrics.deviceshare_whole_free_devices.set(
            float(manager.whole_free_devices()), labels=self._tl())
        return refused

    # koordlint: guarded-by(self.lock)
    def _unbind_for_devices(self, pod: PodSpec, node: str,
                            result: SchedulingResult) -> None:
        """Unreserve a bind whose device grant failed at the commit: its
        node accounting and its quota charge (a converted nomination's
        too: it was charged when assumed) go back."""
        from koordinator_tpu.scheduler.diagnosis import device_refusal

        bp = self.bound.pop(pod.name)
        self._release_bound_capacity(bp)
        self._charge_quota_used(pod, sign=-1)
        result.assignments.pop(pod.name, None)
        self.pending[pod.name] = pod
        self._pending_rev += 1
        result.failures[pod.name] = device_refusal(
            len(self.snapshot.node_index), node)
        metrics.deviceshare_grants.inc(labels={"outcome": "no_device"})

    def _count_device_outcomes(self, pods, a: np.ndarray,
                               grants: _RoundGrants) -> None:
        """``deviceshare_grants_total`` for a round whose solve carried
        the device stage, per proposal that reached it: granted, undone
        because a pod ahead in the same round took the device
        (lost_race), or left unassigned with a device ask (no_device)."""
        p = len(pods)
        granted = int(grants.selection[:p].any(axis=1).sum())
        lost = int(grants.lost_races[:p].sum())
        wants = np.array([_wants_device(pod) for pod in pods])
        unplaced = int((wants & (a[:p] < 0)).sum())
        for outcome, n in (("granted", granted), ("lost_race", lost),
                           ("no_device", unplaced)):
            if n:
                metrics.deviceshare_grants.inc(n, labels={"outcome": outcome})

    def _allocate_fine_grained(self, pod: PodSpec, node: str) -> None:
        """Reserve-phase cpuset allocation (nodenumaresource Reserve:
        resource_manager.go:357 allocateCPUSet).  A cpuset that cannot be
        satisfied degrades to the shared pool / no pinning rather than
        failing an already-committed bind — the koordlet share-pool hook
        still applies its per-QoS cpuset.  Devices do NOT degrade: their
        Reserve is :meth:`_grant_devices`, before this, and a pod it
        refuses never gets here."""
        from koordinator_tpu.api.qos import QoSClass
        from koordinator_tpu.api.resources import ResourceDim

        status: dict[str, dict] = self.resource_status.pop(pod.name, {})
        if (self.cpu_manager is not None
                and int(pod.qos) in (int(QoSClass.LSR), int(QoSClass.LSE))
                and self.cpu_manager.node(node) is not None):
            from koordinator_tpu.scheduler.cpu_manager import (
                EXCLUSIVE_PCPU_LEVEL,
            )

            cores = int(pod.requests[ResourceDim.CPU]) // 1000
            if cores >= 1:
                cpus = self.cpu_manager.allocate(
                    node, pod.name, cores,
                    exclusive_policy=EXCLUSIVE_PCPU_LEVEL)
                if cpus is not None:
                    status["resource-status"] = (
                        self.cpu_manager.resource_status(node, pod.name))
        if status:
            self.resource_status[pod.name] = status

    def _adapt_device_plugin(self, pod: PodSpec, node: str,
                             status: dict) -> None:
        """DevicePluginAdaption gate: translate the allocation into vendor
        device-plugin annotations (device_plugin_adapter.go:100).  The
        reference fails PreBind on an adapt error; this seam is documented
        degrade-not-fail (see _allocate_fine_grained), so an inexpressible
        allocation records the error on the status instead and skips the
        vendor dialect — operators see it, the bind proceeds unpinned."""
        from koordinator_tpu.features import SCHEDULER_GATES

        if not SCHEDULER_GATES.enabled("DevicePluginAdaption"):
            return
        from koordinator_tpu.scheduler import device_plugin_adapter as dpa

        spec = self.snapshot.node_specs.get(node)
        node_labels = spec.labels if spec is not None else {}
        locks = self._device_node_locks.setdefault(node, {})
        try:
            # the adapter's default wall clock, NOT self.clock: the
            # annotations are UnixNano timestamps consumed by EXTERNAL
            # vendor plugins comparing against time.Now() — a monotonic
            # scheduler clock would stamp the year 1970
            res = dpa.adapt_for_device_plugin(
                status["device-allocated"],
                gpu_vendor=node_labels.get(dpa.LABEL_GPU_VENDOR, ""),
                gpu_model=node_labels.get(dpa.LABEL_GPU_MODEL, ""),
                pod_labels=pod.labels,
                node_annotations=locks,
            )
        except dpa.AdaptError as e:
            status["device-plugin"] = {"error": str(e)}
            return
        if dpa.LABEL_HAMI_VGPU_NODE in res.pod_labels:
            res.pod_labels[dpa.LABEL_HAMI_VGPU_NODE] = node
        locks.update(res.node_annotations)
        status["device-plugin"] = {
            "annotations": res.pod_annotations,
            "labels": res.pod_labels,
            "node_annotations": dict(res.node_annotations),
        }

    def clear_device_node_lock(self, node: str, key: str) -> None:
        """The vendor device plugin finished a pod and removed its node
        lock annotation (device_plugin_adapter.go: 'will automatically
        remove it after allocation of a pod')."""
        self._device_node_locks.get(node, {}).pop(key, None)

    def _release_fine_grained(self, pod_name: str, node: str) -> None:
        if self.cpu_manager is not None:
            self.cpu_manager.release(node, pod_name)
        if self.device_manager is not None:
            # DeviceShare Unreserve: the books alone; the device op of
            # all of a round's releases is the snapshot's next fold
            t0 = time.perf_counter()
            if self.device_manager.release(node, pod_name):
                timeline.RECORDER.add(t0, time.perf_counter(), "host_other",
                                      "release.devices", self.tenant)
        self.resource_status.pop(pod_name, None)

    def _charge_quota_used(self, pod: PodSpec, sign: int) -> None:
        if (pod.quota and self.quota_tree is not None
                and pod.quota in self.quota_tree.nodes):
            q = self.quota_tree.nodes[pod.quota]
            q.used = q.used + sign * pod.requests.astype(np.int64)
            if pod.non_preemptible:
                q.non_preemptible_used = (
                    q.non_preemptible_used + sign * pod.requests.astype(np.int64)
                )

    # -- nominated pods (nominatedNodeName semantics) -----------------------

    def _nomination_assume(self, pod: PodSpec, node: str) -> None:
        """Account a nomination: reserve the node AND charge the quota, so
        neither the victims' freed capacity nor the quota headroom can be
        double-spent before the preemptor binds."""
        self.snapshot.reserve(node, pod.requests)
        self._charge_quota_used(pod, sign=1)
        self.nominations[pod.name] = node
        self._nomination_gen[pod.name] = (
            self.snapshot.node_generation.get(node, 0))

    def _nomination_release(self, pod: PodSpec) -> None:
        """Undo :meth:`_nomination_assume` (stale nomination / pod deleted)."""
        node = self.nominations.pop(pod.name, None)
        if node is None:
            return
        self.snapshot.unreserve_instance(
            node, pod.requests, self._nomination_gen.pop(pod.name, 0))
        self._charge_quota_used(pod, sign=-1)

    def _nominated_fit(self, pod: PodSpec, row: int) -> bool:  # koordlint: guarded-by(self.lock)
        """Re-run Filter for a nominated pod on its nominated node (with the
        pod's own assumed accounting already released by the caller)."""
        from koordinator_tpu.ops.assignment import score_pods

        batch = PodBatch.build(
            pod.requests[None].astype(np.int32),
            priority=np.array([pod.priority], np.int32),
            feasible=self.snapshot.feasibility_row(pod)[None],
            node_capacity=self.snapshot.capacity, capacity=16,
        )
        _, feasible = score_pods(self.snapshot.state, batch, self.config)
        if not bool(feasible[0, row]):
            return False
        if pod.quota is not None and self.quota_tree is not None:
            return self.quota_tree.admits(
                pod.quota, pod.requests, pod.non_preemptible
            )
        return True

    def _resolve_nominations(self, result: SchedulingResult) -> None:  # koordlint: guarded-by(self.lock)
        """Fast-path for preemptors nominated in an earlier round.

        A nominated pod's resources were assumed (node reservation + quota
        charge) at preemption time, so nothing else could take the victims'
        freed capacity.  Here each pod's own assumption is briefly released,
        Filter re-runs on the nominated node, and the pod either binds there
        or loses the nomination and rejoins the batch with its full feasible
        set.  Gang members resolve all-or-nothing: if any member's nominated
        node stopped being viable, the whole gang's nominations are released
        (partial gang binds below minMember are never produced)."""
        groups: dict[str, list[PodSpec]] = {}
        for name in list(self.nominations):
            pod = self.pending.get(name)
            if pod is None:
                self.nominations.pop(name, None)  # pod gone; nothing assumed
                self._nomination_gen.pop(name, None)
                continue
            groups.setdefault(pod.gang or f"\0solo:{name}", []).append(pod)

        for members in groups.values():
            assumed: list[tuple[PodSpec, str]] = []  # re-assumed, not yet bound
            ok = True
            for pod in members:
                node_name = self.nominations[pod.name]
                row = self.snapshot.node_index.get(node_name)
                # release own assumption, re-check with peers' still held
                self._nomination_release(pod)
                if row is None or not self._nominated_fit(pod, row):
                    ok = False
                    break
                self._nomination_assume(pod, node_name)
                assumed.append((pod, node_name))
            if ok:
                for pod, node_name in assumed:
                    # assumption becomes the bind accounting (no re-reserve,
                    # no re-charge)
                    self._commit_bind(pod, node_name, result,
                                      charge_quota=False)
            else:
                # release every member still holding an assumption (the
                # failed member already released; release() no-ops for it)
                for pod in members:
                    self._nomination_release(pod)

    # -- preemption (PostFilter) --------------------------------------------

    def _pdb_arrays(self) -> tuple[list[str], np.ndarray]:
        names = sorted(self.pdbs)
        allowed = np.array(
            [self.pdbs[n].allowed for n in names], np.int32
        ).reshape(-1)
        if not names:
            allowed = np.zeros(1, np.int32)  # padded budget row, never matched
        return names, allowed

    def _build_scheduled(self, quota_index: dict[str, int]):
        """The bound pods' columns as a ScheduledPods tensor (+ name
        order).  Rows go in name order, as preemption always saw them."""
        from koordinator_tpu.ops.preemption import ScheduledPods

        pdb_names, _ = self._pdb_arrays()
        cols = self.bound.columns
        names = sorted(self.bound)
        v = len(names)
        slots = cols.slots(names)
        req = cols.requests[slots]
        # a pod bound to a PREVIOUS instance of a re-added node reads -1:
        # its capacity was never charged to the current row, so it must
        # not be a victim candidate — "evicting" it would let the solve
        # assume freed capacity that was never there and nominate a
        # preemptor past allocatable (caught by the preemption churn
        # suite)
        node = cols.node_rows(self.snapshot)[slots]
        pri = cols.priority[slots]
        quota_row = np.array([quota_index.get(q, -1) if q is not None else -1
                              for q in cols.quotas.values], np.int32)
        qid = quota_row[cols.quota_id[slots]]
        nonp = (cols.flags[slots] & bound_columns.NON_PREEMPTIBLE) != 0
        # a pod matching several PDBs carries its most-constraining one
        # (smallest remaining budget) for the violating classification;
        # eviction decrements every matching budget (commit path).  Pods
        # of one label set match alike, so the match runs per label set
        pdb_of_labelset = np.full(len(cols.labelsets), -1, np.int32)
        if pdb_names:
            for ls in np.unique(cols.labelset_id[slots]):
                labels = cols.labels_of(int(ls))
                matches = [pi for pi, pn in enumerate(pdb_names)
                           if self.pdbs[pn].matches(labels)]
                if matches:
                    pdb_of_labelset[ls] = min(
                        matches,
                        key=lambda pi: self.pdbs[pdb_names[pi]].allowed)
        pdb = pdb_of_labelset[cols.labelset_id[slots]]
        return ScheduledPods.build(
            req, node,
            priority=pri if v else None, quota_id=qid if v else None,
            non_preemptible=nonp if v else None,
            pdb_id=pdb if v else None,
        ), names

    def _quota_headroom(self, quota_name: str | None) -> np.ndarray | None:
        """(R,) runtime - used for the pod's quota (postFilterState.usedLimit
        semantics) — victims must bring used back under it."""
        if quota_name is None or self.quota_tree is None:
            return None
        qnode = self.quota_tree.nodes.get(quota_name)
        if qnode is None:
            return None
        from koordinator_tpu.quota.admission import HEADROOM_CLAMP
        from koordinator_tpu.quota.tree import UNBOUNDED

        # dims outside the quota's declared max are unchecked (quotav1.Mask
        # semantics): give them unbounded headroom so a fair-share deficit on
        # an undeclared dim cannot block preemption that admission allows
        hr = np.where(
            qnode.max != UNBOUNDED, qnode.runtime - qnode.used, HEADROOM_CLAMP
        )
        return np.clip(hr, -HEADROOM_CLAMP, HEADROOM_CLAMP).astype(np.int32)

    def _run_preemption(self, pods, batch, result: SchedulingResult) -> None:  # koordlint: guarded-by(self.lock)
        """PostFilter: for each still-unschedulable pod, find a min-cost
        victim set, evict, and nominate.  Gang members preempt all-or-nothing
        (job-level preemption, coscheduling preemption.go:206); quota-rejected
        pods preempt within their quota (elasticquota preempt.go:111)."""
        # reserve-pods don't preempt here: their nominate/bind flow is the
        # reservation lifecycle, not the pod nomination machine
        failed = [p for p in pods if p.name in result.failures
                  and not p.name.startswith(RSV_POD_PREFIX)]
        if not failed:
            return
        quota_index = (
            {} if self.quota_tree is None
            else {n: i for i, n in enumerate(sorted(self.quota_tree.nodes))}
        )
        sched, bound_names = self._build_scheduled(quota_index)
        if not bound_names:
            return
        pdb_names, pdb_allowed = self._pdb_arrays()
        pdb_allowed = jnp.asarray(pdb_allowed)
        state = self.snapshot.state

        # group failed pods: gangs preempt as a job, others individually,
        # highest-priority first
        failed.sort(key=lambda p: (-p.priority, p.creation, p.name))
        jobs: list[list[PodSpec]] = []
        seen_gangs: set[str] = set()
        for p in failed:
            if p.gang is not None:
                if p.gang in seen_gangs:
                    continue
                seen_gangs.add(p.gang)
                jobs.append([q for q in failed if q.gang == p.gang])
            else:
                jobs.append([p])

        # per-round budget (mirror rsv_prepass_cap): a quota-starved 50k
        # queue must not become 50k dry-runs in one round.  Highest-priority
        # jobs first (already sorted); a gang that does not fit the
        # remaining budget is skipped whole (all-or-nothing), the rest
        # retry next round.  Applied BEFORE the O(F·N) mask expansion below
        # so the per-round host cost is O(cap·N), not O(F·N).
        budget = self.preempt_cap
        capped: list[list[PodSpec]] = []
        for job in jobs:
            if budget <= 0:
                break
            if any(p.preemption_policy == "Never" for p in job):
                continue
            if len(job) > budget:
                continue
            capped.append(job)
            budget -= len(job)
        if not capped:
            return

        pod_row = {p.name: i for i, p in enumerate(pods)}
        # expand feasibility + threshold masks only for the capped
        # preemptors (O(cap·N), not O(P·N) — preemption is the rare path)
        from koordinator_tpu.ops import scoring
        from koordinator_tpu.ops.assignment import _threshold_mask

        fail_rows = np.array(
            sorted({pod_row[p.name] for job in capped for p in job}),
            np.int32,
        )
        feasible_np = {
            r: np.asarray(batch.feasible_row(state, int(r)))
            for r in fail_rows
        }
        # preemption cannot lower measured usage, so nodes over the loadaware
        # threshold stay infeasible (the dry-run re-runs Filter in the
        # reference, which includes the usage-threshold check)
        pod_est = scoring.estimate_pod_usage_by_band(
            batch.requests[jnp.asarray(fail_rows)],
            self.config.estimator_factors, self.config.estimator_defaults,
        )
        thr = np.asarray(_threshold_mask(
            self.config, state.node_usage, state.node_agg_usage,
            state.node_allocatable, pod_est,
        ))
        thr_np = {int(r): thr[i] for i, r in enumerate(fail_rows)}

        i = 0
        while i < len(capped):
            job = capped[i]
            if len(job) == 1 and job[0].gang is None:
                # run of consecutive single-pod preemptors: one jitted
                # chain dispatch instead of one dispatch per pod
                chunk: list[PodSpec] = []
                while (i < len(capped) and len(capped[i]) == 1
                       and capped[i][0].gang is None
                       and len(chunk) < self.preempt_chunk):
                    chunk.append(capped[i][0])
                    i += 1
                state, sched, pdb_allowed = self._run_preempt_chunk(
                    chunk, state, sched, pdb_allowed, quota_index,
                    bound_names, pod_row, feasible_np, thr_np, result,
                )
                continue
            i += 1
            state, sched, pdb_allowed = self._run_preempt_job(
                job, state, sched, pdb_allowed, quota_index, bound_names,
                pod_row, feasible_np, thr_np, result,
            )

    def _run_preempt_job(
        self, job, state, sched, pdb_allowed, quota_index, bound_names,
        pod_row, feasible_np, thr_np, result,
    ):
        """One gang (or host-path single) job: sequential dry-runs with
        all-or-nothing commit.  Returns the evolved (state, sched, pdb)."""
        from koordinator_tpu.quota.admission import HEADROOM_CLAMP

        cur_state, cur_sched, cur_pdb = state, sched, pdb_allowed
        outcomes = []
        # quota consumed/freed by this job's earlier members (nominated
        # requests minus same-quota victims): the tree is only charged at
        # commit, so the dry run must not double-spend headroom
        job_assumed: dict[str, np.ndarray] = {}
        for p in job:
            quota_hr = self._quota_headroom(p.quota)
            same_quota = quota_hr is not None
            if same_quota and p.quota in job_assumed:
                quota_hr = np.clip(
                    quota_hr.astype(np.int64) - job_assumed[p.quota],
                    -HEADROOM_CLAMP, HEADROOM_CLAMP,
                ).astype(np.int32)
            qid = quota_index.get(p.quota, -1) if p.quota else -1
            # feasibility row from the solve batch (affinity/selector)
            # ANDed with the usage-threshold filter; preemption fixes
            # neither affinity nor measured-load failures
            row = feasible_np[pod_row[p.name]] & thr_np[pod_row[p.name]]
            out = self._preempt(
                cur_state, cur_sched,
                jnp.asarray(p.requests.astype(np.int32)),
                jnp.int32(p.priority), jnp.int32(qid),
                jnp.asarray(row), cur_pdb,
                quota_headroom=(
                    jnp.asarray(quota_hr) if same_quota else None
                ),
                same_quota_only=same_quota,
            )
            node_row = int(out.node)
            if node_row < 0:
                # all-or-nothing: drop the job's tentative evictions
                return state, sched, pdb_allowed
            victim_names = [
                bound_names[v]
                for v in np.flatnonzero(np.asarray(out.victims))
            ]
            outcomes.append((p, int(out.node), victim_names))
            if p.quota is not None:
                delta = p.requests.astype(np.int64)
                for vname in victim_names:
                    bp = self.bound[vname]
                    if bp.quota == p.quota:
                        delta = delta - bp.requests.astype(np.int64)
                job_assumed[p.quota] = (
                    job_assumed.get(p.quota, 0) + delta
                )
            cur_state, cur_sched, cur_pdb = out.state, out.sched, out.pdb_allowed

        # commit: evict victims, record nominations, update diagnosis.
        # Later jobs see this job's evictions + nominations; bound_names
        # order is unchanged (evicted rows are invalid in sched).
        for p, node_row, victim_names in outcomes:
            self._commit_one_preemption(p, node_row, victim_names, result)
        return cur_state, cur_sched, cur_pdb

    def _run_preempt_chunk(
        self, chunk, state, sched, pdb_allowed, quota_index, bound_names,
        pod_row, feasible_np, thr_np, result,
    ):
        """A run of single-pod preemptors in ONE jitted chain dispatch
        (ops/preemption.preempt_chain).  Semantics match calling
        :meth:`_run_preempt_job` per pod; the chunk is padded to
        ``preempt_chunk`` rows so chain lengths don't retrace."""
        from koordinator_tpu.quota.admission import HEADROOM_CLAMP

        c = self.preempt_chunk
        r = chunk[0].requests.shape[0]
        n = self.snapshot.capacity
        reqs = np.zeros((c, r), np.int32)
        pris = np.zeros(c, np.int32)
        qids = np.full(c, -1, np.int32)
        feas = np.zeros((c, n), bool)
        same_q = np.zeros(c, bool)
        active = np.zeros(c, bool)
        # (Q, R) runtime - used per quota row; rows the chunk never touches
        # stay fully open
        q_rows = max(len(quota_index), 1)
        base_hr = np.full((q_rows, r), HEADROOM_CLAMP, np.int32)
        for name, qi in quota_index.items():
            hr = self._quota_headroom(name)
            if hr is not None:
                base_hr[qi] = hr
        for j, p in enumerate(chunk):
            reqs[j] = p.requests.astype(np.int32)
            pris[j] = p.priority
            qids[j] = quota_index.get(p.quota, -1) if p.quota else -1
            feas[j] = feasible_np[pod_row[p.name]] & thr_np[pod_row[p.name]]
            same_q[j] = self._quota_headroom(p.quota) is not None
            active[j] = True

        out = self._preempt_chain(
            state, sched, jnp.asarray(reqs), jnp.asarray(pris),
            jnp.asarray(qids), jnp.asarray(feas), jnp.asarray(same_q),
            jnp.asarray(active), pdb_allowed, jnp.asarray(base_hr),
        )
        nodes = np.asarray(out.node)
        victims = np.asarray(out.victims)
        for j, p in enumerate(chunk):
            if nodes[j] < 0:
                continue
            victim_names = [
                bound_names[v] for v in np.flatnonzero(victims[j])
            ]
            self._commit_one_preemption(p, int(nodes[j]), victim_names,
                                        result)
        return out.state, out.sched, out.pdb_allowed

    def _commit_one_preemption(
        self, p, node_row: int, victim_names: list[str], result,
    ) -> None:
        """Host commit for one successful preemptor: evict victims (free
        capacity, release quota, charge PDBs, call preempt_fn), assume the
        preemptor's nomination, and record it on the round result."""
        node_name = self.snapshot.node_name(node_row)
        for vname in victim_names:
            bp = self.bound.pop(vname)
            # shared freeing: fine-grained allocations and
            # reservation-aware unreserve (a reservation-backed
            # victim returns its drawn vector, not raw capacity)
            self._release_bound_capacity(bp)
            if bp.quota and self.quota_tree is not None \
                    and bp.quota in self.quota_tree.nodes:
                q = self.quota_tree.nodes[bp.quota]
                q.used = q.used - bp.requests.astype(np.int64)
                if bp.non_preemptible:
                    q.non_preemptible_used = (
                        q.non_preemptible_used
                        - bp.requests.astype(np.int64)
                    )
            # every matching PDB pays for the disruption
            for pn in self.pdbs:
                if self.pdbs[pn].matches(bp.labels):
                    self.pdbs[pn].allowed -= 1
            if self.preempt_fn is not None:
                self.preempt_fn(vname, p.name)
        # assume the preemptor's resources (node reservation + quota
        # charge): nothing may claim the freed capacity or headroom
        # before the preemptor binds or the nomination is cleared
        self._nomination_assume(p, node_name)
        result.nominations[p.name] = (node_name, victim_names)
        diag = result.failures.get(p.name)
        if diag is not None:
            diag.preempt_node = node_name
            diag.preempt_victims = victim_names
