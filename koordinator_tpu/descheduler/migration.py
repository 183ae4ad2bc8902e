"""PodMigrationJob controller: arbitrated, reservation-backed migration.

Semantics from ``pkg/descheduler/controllers/migration``:

- Jobs are arbitrated before running (arbitrator/arbitrator.go:51): candidates
  are *sorted* (earlier creation first, lower-priority pods first) then
  *filtered* by stability group limits — max concurrent migrations per node /
  namespace / owning workload, and the workload's max-unavailable budget
  (arbitrator/filter.go).
- A reservation for the replacement pod can be requested before eviction
  (migration/reservation/): the job only proceeds to eviction once capacity
  is reserved, so the migrated pod cannot be left homeless.
- Eviction runs through a pluggable evictor (eviction API / delete / soft
  label, migration/evictor/*.go); the job tracks phase + conditions and
  times out.

This is control-plane protocol machinery, so it stays host-side Python; the
expensive part — choosing where replacements go — is delegated to the TPU
solver through the ``reserve_many`` callback: ALL the jobs a reconcile lets
run are handed over at once, so that against the scheduler they are one
batched round of reserve-pods and not a round per job.  One job is a batch
of one.
"""

from __future__ import annotations

import dataclasses
import enum
import time
from collections import Counter
from typing import Callable, Iterable


class MigrationJobPhase(str, enum.Enum):
    PENDING = "Pending"
    RUNNING = "Running"
    SUCCEEDED = "Succeeded"
    FAILED = "Failed"


@dataclasses.dataclass
class MigrationJob:
    """PodMigrationJob (apis/scheduling/v1alpha1/pod_migration_job_types.go)."""

    name: str
    pod: str
    node: str
    namespace: str = "default"
    workload: str = ""
    priority: int = 0
    create_time: float = dataclasses.field(default_factory=time.monotonic)
    timeout_sec: float = 600.0
    phase: MigrationJobPhase = MigrationJobPhase.PENDING
    reason: str = ""
    reservation: str | None = None
    start_time: float | None = None


@dataclasses.dataclass
class ArbitrationLimits:
    """Group limits (arbitrator/filter.go defaults). The per-workload specs
    are int-or-percent (e.g. 2 or "10%") resolved against the workload's
    expected replicas via :func:`get_max_unavailable`; None means "use the
    replica-count-dependent default"."""

    max_migrating_per_node: int = 2
    max_migrating_per_namespace: int = 10
    max_migrating_per_workload: int | str | None = None
    max_unavailable_per_workload: int | str | None = None


def scaled_int_or_percent(spec: int | str, replicas: int) -> int:
    """intstr.GetScaledValueFromIntOrPercent, round-down."""
    if isinstance(spec, str):
        if not spec.endswith("%"):
            raise ValueError(f"invalid int-or-percent {spec!r}")
        return replicas * int(spec[:-1]) // 100
    return int(spec)


def get_max_unavailable(replicas: int, spec: int | str | None) -> int:
    """migration/util/util.go:81 GetMaxUnavailable: resolve the spec against
    replicas (a percent that floors to 0 becomes 1); an absent/zero spec
    defaults to 10% above 10 replicas, 2 for 4-10, else 1; capped at
    replicas."""
    max_unavailable = 0
    if spec is not None:
        max_unavailable = scaled_int_or_percent(spec, replicas)
        if max_unavailable == 0:
            max_unavailable = 1  # a percent flooring to 0 still allows one
    if max_unavailable == 0:
        if replicas > 10:
            max_unavailable = replicas * 10 // 100
        elif 4 <= replicas <= 10:
            max_unavailable = 2
        else:
            max_unavailable = 1
    return min(max_unavailable, replicas)


def get_max_migrating(replicas: int, spec: int | str | None) -> int:
    """migration/util/util.go:116 — same resolution as max-unavailable."""
    return get_max_unavailable(replicas, spec)


@dataclasses.dataclass
class Workload:
    """What the controllerfinder resolves for an owner ref
    (pkg/util/controllerfinder: GetPodsForRef → expected replicas; the
    workload's own rollout maxUnavailable when it declares one)."""

    ref: str                               # "Kind/name"
    expected_replicas: int
    max_unavailable: int | str | None = None   # workload spec override
    unavailable: int = 0                   # currently not-ready pods


class ControllerFinder:
    """Resolves a pod's owning workload to (replicas, budgets) — the
    reference's controllerfinder seam, fed by the states informer here."""

    def __init__(self) -> None:
        self._workloads: dict[str, Workload] = {}

    def register(self, workload: Workload) -> None:
        self._workloads[workload.ref] = workload

    def get(self, ref: str) -> Workload | None:
        return self._workloads.get(ref)


class MigrationController:
    """Reconciles MigrationJobs with arbitration and reservation-first flow."""

    def __init__(
        self,
        limits: ArbitrationLimits | None = None,
        reserve_many: Callable[[list[MigrationJob]],
                               dict[str, str | None]] | None = None,
        evict_fn: Callable[[MigrationJob], bool] | None = None,
        workload_unavailable_fn: Callable[[str], int] | None = None,
        controller_finder: ControllerFinder | None = None,
        clock: Callable[[], float] = time.monotonic,
    ):
        self.limits = limits or ArbitrationLimits()
        #: ``reserve_many(jobs) -> {job name: reservation name | None}``:
        #: replacement capacity for every job of one reconcile, secured
        #: BEFORE any of them evicts; None fails the job
        self.reserve_many = reserve_many
        self.evict_fn = evict_fn
        self.workload_unavailable_fn = workload_unavailable_fn
        self.controller_finder = controller_finder
        self.clock = clock
        self.jobs: dict[str, MigrationJob] = {}
        #: pod -> how many live (Pending or Running) jobs name it
        self._live: Counter = Counter()

    def _workload_budgets(self, ref: str) -> tuple[int, int, int]:
        """(max_migrating, max_unavailable, already_unavailable) for the
        owning workload — replica-scaled when the controllerfinder knows it
        (filter.go:409 filterMaxMigratingOrUnavailablePerWorkload), flat
        config values otherwise."""
        lim = self.limits
        workload = (self.controller_finder.get(ref)
                    if self.controller_finder else None)
        if workload is not None:
            replicas = workload.expected_replicas
            max_migrating = get_max_migrating(
                replicas, lim.max_migrating_per_workload)
            spec = (workload.max_unavailable
                    if workload.max_unavailable is not None
                    else lim.max_unavailable_per_workload)
            max_unavailable = get_max_unavailable(replicas, spec)
            unavailable = workload.unavailable
        else:
            def flat(spec, default=2):
                return spec if isinstance(spec, int) and spec > 0 else default
            max_migrating = flat(lim.max_migrating_per_workload)
            max_unavailable = flat(lim.max_unavailable_per_workload)
            unavailable = 0
        if self.workload_unavailable_fn is not None:
            unavailable = self.workload_unavailable_fn(ref)
        return max_migrating, max_unavailable, unavailable

    # -- API ---------------------------------------------------------------

    def submit(self, job: MigrationJob) -> None:
        if job.name in self.jobs:
            raise ValueError(f"migration job {job.name!r} already exists")
        self.jobs[job.name] = job
        if job.phase in (MigrationJobPhase.PENDING, MigrationJobPhase.RUNNING):
            self._live[job.pod] += 1

    def _finish(self, job: MigrationJob, phase: MigrationJobPhase,
                reason: str) -> None:
        job.phase, job.reason = phase, reason
        self._live[job.pod] -= 1
        if self._live[job.pod] <= 0:
            del self._live[job.pod]

    def running(self) -> list[MigrationJob]:
        return [j for j in self.jobs.values()
                if j.phase is MigrationJobPhase.RUNNING]

    def pending(self) -> list[MigrationJob]:
        return [j for j in self.jobs.values()
                if j.phase is MigrationJobPhase.PENDING]

    def migrating_pods(self):
        """The pods a live (Pending or Running) job names, as a set-like
        view kept as jobs come and end: the evictor filter keeps them out
        of the next round's victims (filterExistingPodMigrationJob)."""
        return self._live.keys()

    # -- arbitration (sort + filter) ---------------------------------------

    def _sorted_candidates(self) -> list[MigrationJob]:
        """arbitrator/sort.go: stable order — older jobs first, lower pod
        priority migrates first (cheaper disruption)."""
        return sorted(self.pending(), key=lambda j: (j.priority, j.create_time))

    def _group_counts(self, jobs: Iterable[MigrationJob]) -> tuple[Counter, Counter, Counter]:
        node, ns, workload = Counter(), Counter(), Counter()
        for j in jobs:
            node[j.node] += 1
            ns[j.namespace] += 1
            if j.workload:
                workload[j.workload] += 1
        return node, ns, workload

    def arbitrate(self) -> list[MigrationJob]:
        """Pick pending jobs allowed to run this round (sort then filter)."""
        from koordinator_tpu import metrics

        node, ns, workload = self._group_counts(self.running())
        allowed: list[MigrationJob] = []
        outcomes = Counter()
        lim = self.limits
        for job in self._sorted_candidates():
            if node[job.node] >= lim.max_migrating_per_node:
                outcomes["node"] += 1
                continue
            if ns[job.namespace] >= lim.max_migrating_per_namespace:
                outcomes["namespace"] += 1
                continue
            if job.workload:
                max_migrating, max_unavailable, already_unavailable = (
                    self._workload_budgets(job.workload))
                # migrating pods count as unavailable (filter.go:484
                # mergeUnavailableAndMigratingPods)
                if (workload[job.workload] >= max_migrating
                        or already_unavailable + workload[job.workload]
                        >= max_unavailable):
                    outcomes["workload"] += 1
                    continue
            allowed.append(job)
            node[job.node] += 1
            ns[job.namespace] += 1
            if job.workload:
                workload[job.workload] += 1
        outcomes["allowed"] = len(allowed)
        for outcome, count in outcomes.items():
            if count:
                metrics.migration_jobs_arbitrated.inc(
                    count, labels={"outcome": outcome})
        return allowed

    # -- reconcile ---------------------------------------------------------

    def reconcile(self) -> None:
        """One controller round: arbitrate, reserve, evict, expire."""
        from koordinator_tpu import timeline

        tl = timeline.RECORDER
        with tl.section("host_other", "migrate.reconcile"):
            self._reconcile(tl)

    def _reconcile(self, tl) -> None:
        now = self.clock()
        with tl.section("host_other", "migrate.arbitrate",
                        n=len(self.pending())):
            allowed = self.arbitrate()

        # reservation-first: secure replacement capacity before evicting,
        # for all of this round's jobs in one call
        reservations: dict[str, str | None] = {}
        if self.reserve_many is not None and allowed:
            with tl.section("host_other", "migrate.reserve", n=len(allowed)):
                reservations = self.reserve_many(allowed)
        for job in allowed:
            if self.reserve_many is not None:
                reservation = reservations.get(job.name)
                if reservation is None:
                    self._finish(job, MigrationJobPhase.FAILED,
                                 "ReservationFailed")
                    continue
                job.reservation = reservation
            job.phase = MigrationJobPhase.RUNNING
            job.start_time = now

        running = self.running()
        with tl.section("host_other", "migrate.evict", n=len(running)):
            for job in running:
                if self.evict_fn is not None and self.evict_fn(job):
                    self._finish(job, MigrationJobPhase.SUCCEEDED, "Complete")
                elif (job.start_time is not None
                        and now - job.start_time > job.timeout_sec):
                    self._finish(job, MigrationJobPhase.FAILED, "Timeout")

        from koordinator_tpu import metrics

        counts = {phase: 0 for phase in MigrationJobPhase}
        for job in self.jobs.values():
            counts[job.phase] += 1
        for phase, n in counts.items():
            metrics.migration_jobs.set(
                float(n), labels={"phase": phase.value})

    def gc(self, keep: int = 256) -> None:
        """Drop oldest finished jobs beyond the retention limit."""
        finished = sorted(
            (j for j in self.jobs.values()
             if j.phase in (MigrationJobPhase.SUCCEEDED, MigrationJobPhase.FAILED)),
            key=lambda j: j.create_time,
        )
        for j in finished[:-keep] if len(finished) > keep else []:
            del self.jobs[j.name]
