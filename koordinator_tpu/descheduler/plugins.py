"""Descheduler plugins over the framework (reference:
``pkg/descheduler/framework/plugins/``): LowNodeLoad balance bridging the
tensor kernels, custom-priority deschedule, and the migration-controller
evict sink.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax.numpy as jnp
import numpy as np

from koordinator_tpu import metrics, timeline
from koordinator_tpu.descheduler import lownodeload as lnl
from koordinator_tpu.descheduler.framework import Handle, PodInfo
from koordinator_tpu.descheduler.migration import MigrationController, MigrationJob


@dataclasses.dataclass
class VictimUniverse:
    """One round's inputs to victim selection: the node tensors, and the
    pods as parallel columns over indices ``[0, P)``."""

    usage: object            # (N, R) node usage, device or host
    capacity: object         # (N, R)
    node_valid: object       # (N,)
    pod_node: np.ndarray     # (P,) int32 node row, -1 none
    pod_usage: np.ndarray    # (P, R) int32
    pod_priority: np.ndarray # (P,) int32
    evictable: np.ndarray    # (P,) bool: passed the profile's evictor filter
    pod_names: list          # [P] pod keys (None in a dead slot)
    pod_at: Callable[[int], PodInfo]


class SchedulerSource:
    """The universe from the scheduler this descheduler runs beside: the
    node tensors are the device-resident ``ClusterState``'s own
    (``node_usage``, allocatable, valid: no copy, no host round trip), the
    pods are the bound pods' columns (``scheduler/bound_columns.py``) and
    the evictable mask is computed over them.  All of it is read under
    ``Scheduler.lock``, which :meth:`lock` hands to the plugin for the
    whole of staging and selection: a flush donates the state's buffers."""

    def __init__(self, scheduler):
        self.scheduler = scheduler

    def lock(self):
        return self.scheduler.lock

    def pod_info(self, slot: int) -> PodInfo:
        return pod_info_of(self.scheduler.bound.columns, slot)

    def universe(self, handle: Handle) -> VictimUniverse:
        sched = self.scheduler
        sched.snapshot.flush()
        state, cols = sched.snapshot.state, sched.bound.columns
        evictor_filter = _filter_of(handle)
        return VictimUniverse(
            usage=state.node_usage, capacity=state.node_allocatable,
            node_valid=state.node_valid,
            pod_node=cols.node_rows(sched.snapshot),
            pod_usage=cols.pod_usage(),
            pod_priority=cols.priority[: cols.size],
            evictable=(cols.live[: cols.size].copy() if evictor_filter is None
                       else evictor_filter.mask(cols, self.pod_info)),
            pod_names=cols.names, pod_at=self.pod_info)


def _filter_of(handle: Handle):
    from koordinator_tpu.descheduler.framework import _ProfileHandle

    return (handle.profile.evictor_filter
            if isinstance(handle, _ProfileHandle) else None)


def pod_info_of(cols, slot: int) -> PodInfo:
    """The descheduler's view of the bound pod in ``slot`` of the
    columns."""
    from koordinator_tpu.api import extension as ext
    from koordinator_tpu.api.qos import QoSClass
    from koordinator_tpu.scheduler import bound_columns as bc

    name = cols.names[slot]
    flags = int(cols.flags[slot])
    try:
        qos = QoSClass(int(cols.qos[slot])).name
    except ValueError:
        qos = "NONE"
    return PodInfo(
        uid=name, name=name,
        namespace=cols.namespaces.values[cols.namespace_id[slot]],
        node=cols.nodes.values[cols.node_id[slot]],
        priority=int(cols.priority[slot]), qos_class=qos,
        owner=cols.workloads.values[cols.workload_id[slot]],
        labels=cols.labels_of(int(cols.labelset_id[slot])),
        annotations=({ext.ANNOTATION_EVICTION_COST: "-2147483648"}
                     if flags & bc.EVICT_FORBIDDEN else {}),
        is_daemonset=bool(flags & bc.DAEMONSET),
        has_local_storage=bool(flags & bc.LOCAL_STORAGE))


def bound_pods_fn(scheduler) -> Callable[[], list[PodInfo]]:
    """``pods_fn`` for a descheduler beside a scheduler: every bound pod
    as the plugins that walk pods one by one see it."""

    def pods() -> list[PodInfo]:
        with scheduler.lock:
            cols = scheduler.bound.columns
            return [pod_info_of(cols, int(slot))
                    for slot in np.flatnonzero(cols.live[: cols.size])]

    return pods


class LowNodeLoadPlugin:
    """Balance plugin: classify by real utilization, evict from anomalous hot
    nodes into the cold pool's head-room — all selection math on-device
    (lownodeload kernels), eviction through the profile's filter+evictor.

    The plugin runs beside a scheduler and reads that scheduler's state
    (:class:`SchedulerSource`); the walk runs over the pods of abnormal
    nodes only (``lownodeload.SourceNodeSelector``).
    """

    name = "LowNodeLoad"

    def __init__(self, scheduler,
                 args: Optional[lnl.LowNodeLoadArgs] = None):
        self.source = SchedulerSource(scheduler)
        self.args = args or lnl.LowNodeLoadArgs.default()
        self.selector = lnl.SourceNodeSelector(self.args)

    def balance(self, handle: Handle) -> int:
        tl = timeline.RECORDER
        with tl.section("host_other", "desched.round"):
            with self.source.lock():
                with tl.section("host_other", "desched.stage"):
                    u = self.source.universe(handle)
                # one span of n = the candidates walked
                t_select = tl.open("desched.select")
                candidates = ()
                try:
                    abnormal, handles = self.selector.observe(
                        u.usage, u.capacity, u.node_valid)
                    on_node = u.pod_node >= 0
                    candidates = np.flatnonzero(
                        u.evictable & on_node
                        & abnormal[np.where(on_node, u.pod_node, 0)])
                    # equally cheap pods (same priority, same CPU usage)
                    # go in the order of their names: where a pod sits in
                    # the columns is this process's accident, and which
                    # of two such pods leaves must not hang on it
                    candidates = np.asarray(
                        sorted(candidates.tolist(),
                               key=u.pod_names.__getitem__), np.int64)
                    victims = candidates[self.selector.walk(
                        handles, u.pod_node, u.pod_usage, u.pod_priority,
                        candidates)]
                finally:
                    tl.close(t_select, "host_other", n=len(candidates))
                pods = [u.pod_at(int(i)) for i in victims]
            metrics.descheduler_victims_total.inc(
                len(pods), labels={"plugin": self.name})
            with tl.section("host_other", "desched.submit", n=len(pods)):
                return sum(handle.evict(pod, self.name) for pod in pods)


class FragmentationAwarePlugin:
    """Balance plugin (plugins/fragmentationaware): evict the pods whose
    removal most reduces per-node resource-fraction stddev. Scoring and
    greedy selection run on-device (fragmentationaware kernels).

    ``state_fn`` returns (requested(N,R), allocatable(N,R), node_valid(N,),
    node_names[N]); ``pod_requests_fn(pod)`` a (R,) milli-unit vector.
    """

    name = "FragmentationAware"

    def __init__(
        self,
        state_fn: Callable[[], tuple[np.ndarray, np.ndarray, np.ndarray, list[str]]],
        pod_requests_fn: Callable[[PodInfo], np.ndarray],
        resource_mask: Optional[np.ndarray] = None,
        imbalance_threshold: float = 0.2,
        min_gain: float = 0.05,
        max_victims: int = 16,
    ):
        self.state_fn = state_fn
        self.pod_requests_fn = pod_requests_fn
        self.resource_mask = resource_mask
        self.imbalance_threshold = imbalance_threshold
        self.min_gain = min_gain
        self.max_victims = max_victims

    def balance(self, handle: Handle) -> int:
        from koordinator_tpu.descheduler import fragmentationaware as frag
        from koordinator_tpu.descheduler.framework import _ProfileHandle

        requested, allocatable, node_valid, node_names = self.state_fn()
        node_index = {name: i for i, name in enumerate(node_names)}
        pods = [p for p in handle.pods() if p.node in node_index]
        if not pods:
            return 0
        pod_node = np.asarray([node_index[p.node] for p in pods], np.int32)
        pod_requests = np.stack([self.pod_requests_fn(p) for p in pods])
        if isinstance(handle, _ProfileHandle):
            evictable = np.asarray(
                [handle.profile.evictor_filter.filter(p)[0] for p in pods]
            )
        else:
            evictable = np.ones(len(pods), bool)
        mask = (jnp.asarray(self.resource_mask)
                if self.resource_mask is not None
                else frag.default_resource_mask())

        victims = np.asarray(frag.select_victims(
            jnp.asarray(requested), jnp.asarray(allocatable),
            jnp.asarray(node_valid), jnp.asarray(pod_node),
            jnp.asarray(pod_requests), jnp.asarray(evictable), mask,
            imbalance_threshold=self.imbalance_threshold,
            min_gain=self.min_gain, max_victims=self.max_victims,
        ))
        evicted = 0
        for pod, is_victim in zip(pods, victims):
            if is_victim and handle.evict(pod, "FragmentationAware"):
                evicted += 1
        return evicted


class CustomPriorityPlugin:
    """Deschedule plugin (plugins/custompriority): evict pods below a
    priority floor from matching nodes (cleanup of stale low-priority work)."""

    name = "CustomPriority"

    def __init__(self, priority_floor: int,
                 node_filter: Optional[Callable[[str], bool]] = None):
        self.priority_floor = priority_floor
        self.node_filter = node_filter

    def deschedule(self, handle: Handle) -> int:
        evicted = 0
        for pod in handle.pods():
            if pod.priority >= self.priority_floor:
                continue
            if self.node_filter and not self.node_filter(pod.node):
                continue
            if handle.evict(pod, "CustomPriority"):
                evicted += 1
        return evicted


def migration_evict_fn(controller: MigrationController,
                       clock=None) -> Callable[[PodInfo], bool]:
    """Evict sink that creates PodMigrationJobs instead of direct eviction —
    the reference's 'evictor plugin = migration controller' wiring
    (SURVEY.md 3.4)."""
    counter = [0]

    def evict(pod: PodInfo) -> bool:
        counter[0] += 1
        job = MigrationJob(
            name=f"migrate-{pod.uid}-{counter[0]}",
            pod=pod.uid, node=pod.node, namespace=pod.namespace,
            workload=pod.owner, priority=pod.priority,
        )
        try:
            controller.submit(job)
        except ValueError:
            return False
        return True

    return evict


def scheduler_reserve_many(
    scheduler, ttl_sec: float = 1800.0
) -> Callable[[list[MigrationJob]], dict[str, str | None]]:
    """Reservation-first arbitration against the in-process scheduler
    (migration/reservation.go: secure replacement capacity BEFORE evicting),
    for all the jobs of one reconcile at once: one Reservation per job,
    sized to the migrating pod and owned by its labels or workload, ONE
    round that places them all as reserve-pods (by the round's exact
    reservation pre-pass), then one read per job.  Placement back on the source node is rejected —
    a migration must move the pod — and a failed placement cleans the
    reservation up, so a job that comes back None leaves nothing charged.

    The reservation is allocate-once (it backs exactly one replacement pod;
    its charge then lives and dies with that pod) with a TTL so a
    replacement that never arrives can't hide capacity forever."""
    from koordinator_tpu.scheduler.reservations import (
        OwnerMatcher,
        ReservationPhase,
        ReservationSpec,
    )

    def reserve_many(jobs: list[MigrationJob]) -> dict[str, str | None]:
        out: dict[str, str | None] = {job.name: None for job in jobs}
        asked: list[tuple[MigrationJob, str, str]] = []
        with scheduler.lock:
            for job in jobs:
                bound = scheduler.bound.get(job.pod)
                if bound is None:
                    continue
                owners = ([OwnerMatcher(labels=dict(bound.labels))]
                          if bound.labels else [])
                if not owners and job.workload:
                    owners = [OwnerMatcher(controller=job.workload)]
                if not owners:
                    continue
                name = f"migrate-{job.name}"
                scheduler.add_reservation(ReservationSpec(
                    name=name, requests=np.asarray(bound.requests),
                    owners=owners, allocate_once=True, ttl_sec=ttl_sec))
                asked.append((job, name, bound.node))
            if asked:
                scheduler.schedule_round()
                metrics.migration_reserve_rounds.inc()
            for job, name, source in asked:
                spec = scheduler.reservations.get(name)
                if (spec is not None
                        and spec.phase is ReservationPhase.AVAILABLE
                        and spec.node != source):
                    out[job.name] = name
                else:
                    scheduler.remove_reservation(name)
        return out

    return reserve_many


class BoundOwnersFinder:
    """The controllerfinder seam fed from the scheduler's bound pods: a
    workload's expected replicas are the pods bound under its owner ref
    now (counted over the bound pods' columns, once per change of them)."""

    def __init__(self, scheduler):
        self.scheduler = scheduler
        self._counted_at = -1
        self._replicas = np.zeros(0, np.int64)

    def get(self, ref: str):
        from koordinator_tpu.descheduler.migration import Workload

        with self.scheduler.lock:
            cols = self.scheduler.bound.columns
            if cols.version != self._counted_at:
                n = cols.size
                self._replicas = np.bincount(
                    cols.workload_id[:n][cols.live[:n]],
                    minlength=len(cols.workloads))
                self._counted_at = cols.version
            wid = cols.workloads.ids.get(ref)
        if not ref or wid is None or not self._replicas[wid]:
            return None
        return Workload(ref=ref, expected_replicas=int(self._replicas[wid]))


def scheduler_migration_evict_fn(scheduler) -> Callable[[MigrationJob], bool]:
    """evict_fn for :class:`MigrationController` against the in-process
    scheduler: the bound pod releases its capacity (and quota) the way an
    informer pod-delete would."""

    def evict(job: MigrationJob) -> bool:
        scheduler.delete_pod(job.pod)
        return True

    return evict
