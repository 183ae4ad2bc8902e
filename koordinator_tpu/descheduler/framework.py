"""Descheduler plugin framework (reference: ``pkg/descheduler/framework/
types.go:78-98`` — DeschedulePlugin / BalancePlugin / EvictPlugin /
FilterPlugin; profiles ``profile/``; runtime registry ``framework/runtime/``;
eviction plumbing with PDB respect ``evictions/``; evictor modes
``controllers/migration/evictor/``).

A profile bundles plugins; the descheduler loop runs every profile's
Deschedule then Balance plugins each interval. Evictions flow through the
:class:`EvictorFilter` (PDB budgets, priority threshold, owner-kind guards)
and then one of the evictor modes (eviction API / delete / soft label —
represented by pluggable sinks).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Optional, Protocol

from koordinator_tpu.api import extension as ext


@dataclasses.dataclass(frozen=True)
class PodInfo:
    """Descheduler-side pod view."""

    uid: str
    name: str
    namespace: str
    node: str
    priority: int = 0
    qos_class: str = "NONE"
    owner: str = ""                  # workload ref "Kind/name"
    labels: dict = dataclasses.field(default_factory=dict)
    annotations: dict = dataclasses.field(default_factory=dict)
    is_daemonset: bool = False
    has_local_storage: bool = False
    # fields consumed by the upstream-port plugins (descheduler/upstream.py)
    created: float = 0.0                 # creation timestamp (epoch seconds)
    phase: str = "Running"               # Pending/Running/Succeeded/Failed
    reason: str = ""                     # status.reason (e.g. OOMKilled)
    restart_count: int = 0
    images: tuple = ()                   # container image names
    node_selector: dict = dataclasses.field(default_factory=dict)
    # required node affinity: list of terms; a term is a tuple of
    # (key, op, values) expressions, op in {In, NotIn, Exists, DoesNotExist};
    # the pod fits a node if ANY term has ALL expressions matching
    required_affinity: tuple = ()
    # tolerations: (key, operator, value, effect); operator Equal/Exists,
    # empty key + Exists tolerates everything, empty effect matches all
    tolerations: tuple = ()
    # anti-affinity terms owned by THIS pod: (selector dict, topology_key)
    anti_affinity: tuple = ()
    # topology spread constraints: (topology_key, max_skew, selector dict)
    spread_constraints: tuple = ()


@dataclasses.dataclass
class PDB:
    """PodDisruptionBudget relevant state."""

    selector: dict
    disruptions_allowed: int


class Handle(Protocol):
    """What plugins get (framework/types.go Handle): state + evictor."""

    def pods(self) -> list[PodInfo]: ...

    def evict(self, pod: PodInfo, reason: str) -> bool: ...


class DeschedulePlugin(Protocol):
    name: str

    def deschedule(self, handle: Handle) -> int: ...


class BalancePlugin(Protocol):
    name: str

    def balance(self, handle: Handle) -> int: ...


class EvictorFilter:
    """defaultevictor semantics: which pods may be evicted at all."""

    def __init__(
        self,
        evict_system_critical: bool = False,
        evict_local_storage: bool = False,
        evict_daemonsets: bool = False,
        priority_threshold: Optional[int] = None,
        pdbs: Optional[list[PDB]] = None,
        extra_filters: Optional[list[Callable[[PodInfo], bool]]] = None,
        migrating_fn: Optional[Callable[[], set]] = None,
    ):
        self.evict_system_critical = evict_system_critical
        self.evict_local_storage = evict_local_storage
        self.evict_daemonsets = evict_daemonsets
        self.priority_threshold = priority_threshold
        self.pdbs = list(pdbs or [])
        self.extra_filters = list(extra_filters or [])
        #: uids of the pods a live PodMigrationJob already names: such a
        #: pod is not evicted again (the migration controller's
        #: filterExistingPodMigrationJob)
        self.migrating_fn = migrating_fn

    def _pdb_for(self, pod: PodInfo) -> Optional[PDB]:
        return self._pdb_for_labels(pod.labels)

    def _pdb_for_labels(self, labels: dict) -> Optional[PDB]:
        for pdb in self.pdbs:
            if all(labels.get(k) == v for k, v in pdb.selector.items()):
                return pdb
        return None

    def filter(self, pod: PodInfo) -> tuple[bool, str]:
        """(evictable, reason-if-not)."""
        if pod.is_daemonset and not self.evict_daemonsets:
            return False, "daemonset pod"
        if pod.has_local_storage and not self.evict_local_storage:
            return False, "pod has local storage"
        if (not self.evict_system_critical
                and pod.priority >= 2_000_000_000):
            return False, "system critical priority"
        if (self.priority_threshold is not None
                and pod.priority >= self.priority_threshold):
            return False, "priority above threshold"
        if pod.annotations.get(ext.ANNOTATION_EVICTION_COST, "") == "-2147483648":
            return False, "eviction cost forbids"
        pdb = self._pdb_for(pod)
        if pdb is not None and pdb.disruptions_allowed <= 0:
            return False, "PDB exhausted"
        if self.migrating_fn is not None and pod.uid in self.migrating_fn():
            return False, "migration job exists"
        for fn in self.extra_filters:
            if not fn(pod):
                return False, "plugin filter"
        return True, ""

    def mask(self, cols, pod_at: Callable[[int], PodInfo]) -> "np.ndarray":
        """:meth:`filter` over the bound pods' columns
        (``scheduler/bound_columns.BoundColumns``): (size,) bool, the same
        answer per live slot as ``filter(pod_at(slot))[0]``, with no call
        per pod.  ``extra_filters`` are callables on a pod, so they alone
        are asked pod by pod, of the pods everything else lets through."""
        import numpy as np

        from koordinator_tpu.scheduler import bound_columns as bc

        n = cols.size
        ok = cols.live[:n].copy()
        flags, priority = cols.flags[:n], cols.priority[:n]
        if not self.evict_daemonsets:
            ok &= (flags & bc.DAEMONSET) == 0
        if not self.evict_local_storage:
            ok &= (flags & bc.LOCAL_STORAGE) == 0
        if not self.evict_system_critical:
            ok &= priority < 2_000_000_000
        if self.priority_threshold is not None:
            ok &= priority < self.priority_threshold
        ok &= (flags & bc.EVICT_FORBIDDEN) == 0
        if self.pdbs:
            # pods of one label set meet the same (first matching) PDB
            exhausted = np.zeros(len(cols.labelsets), bool)
            for ls in np.unique(cols.labelset_id[:n][ok]):
                pdb = self._pdb_for_labels(cols.labels_of(int(ls)))
                exhausted[ls] = (pdb is not None
                                 and pdb.disruptions_allowed <= 0)
            ok &= ~exhausted[cols.labelset_id[:n]]
        if self.migrating_fn is not None:
            slots = [cols.slot_of[uid] for uid in self.migrating_fn()
                     if uid in cols.slot_of]
            ok[slots] = False
        if self.extra_filters:
            for slot in np.flatnonzero(ok):
                pod = pod_at(int(slot))
                ok[slot] = all(fn(pod) for fn in self.extra_filters)
        return ok

    def consume_budget(self, pod: PodInfo) -> None:
        pdb = self._pdb_for(pod)
        if pdb is not None:
            pdb.disruptions_allowed -= 1


# ---- evictor modes (migration/evictor/*.go) --------------------------------

MODE_EVICT = "Eviction"        # eviction API (PDB-checked server-side too)
MODE_DELETE = "Delete"         # direct delete
MODE_SOFT = "SoftMigrate"      # annotate only; an external system drains


class Evictor:
    """Eviction executor with pluggable transport per mode."""

    def __init__(self, mode: str = MODE_EVICT,
                 evict_fn: Optional[Callable[[PodInfo], bool]] = None,
                 delete_fn: Optional[Callable[[PodInfo], bool]] = None,
                 label_fn: Optional[Callable[[PodInfo, dict], bool]] = None):
        self.mode = mode
        self.evict_fn = evict_fn
        self.delete_fn = delete_fn
        self.label_fn = label_fn
        self.evicted: list[tuple[str, str]] = []
        self.profile = ""   # stamped by ProfileRunner for metric attribution

    def evict(self, pod: PodInfo, reason: str) -> bool:
        ok = False
        if self.mode == MODE_EVICT:
            ok = self.evict_fn(pod) if self.evict_fn else True
        elif self.mode == MODE_DELETE:
            ok = self.delete_fn(pod) if self.delete_fn else True
        elif self.mode == MODE_SOFT:
            labels = {ext.LABEL_SOFT_EVICTION: reason}
            ok = self.label_fn(pod, labels) if self.label_fn else True
        if ok:
            from koordinator_tpu.metrics import descheduler_evictions_total

            descheduler_evictions_total.inc(
                labels={"profile": self.profile, "reason": reason})
            self.evicted.append((pod.uid, reason))
        return ok


@dataclasses.dataclass
class Profile:
    """One descheduling profile (profile/profile.go)."""

    name: str
    deschedule_plugins: list = dataclasses.field(default_factory=list)
    balance_plugins: list = dataclasses.field(default_factory=list)
    evictor_filter: EvictorFilter = dataclasses.field(default_factory=EvictorFilter)
    evictor: Evictor = dataclasses.field(default_factory=Evictor)
    max_evictions_per_round: int = 0   # 0 = unlimited


class _ProfileHandle:
    def __init__(self, profile: Profile, pods_fn: Callable[[], list[PodInfo]]):
        self.profile = profile
        profile.evictor.profile = profile.name
        self._pods_fn = pods_fn
        self.evictions = 0
        #: uids evicted this round — overlapping plugins (a Failed pod can
        #: match RemoveFailedPods AND PodLifeTime) must not double-evict,
        #: double-decrement PDB budgets, or double-count the round cap
        self._evicted_uids: set[str] = set()

    def pods(self) -> list[PodInfo]:
        return self._pods_fn()

    def evict(self, pod: PodInfo, reason: str) -> bool:
        if pod.uid in self._evicted_uids:
            return False
        limit = self.profile.max_evictions_per_round
        if limit and self.evictions >= limit:
            return False
        ok, _ = self.profile.evictor_filter.filter(pod)
        if not ok:
            return False
        if not self.profile.evictor.evict(pod, reason):
            return False
        self.profile.evictor_filter.consume_budget(pod)
        self.evictions += 1
        self._evicted_uids.add(pod.uid)
        return True


class Descheduler:
    """The loop (pkg/descheduler/descheduler.go): every interval, run each
    profile's Deschedule plugins then Balance plugins."""

    def __init__(self, profiles: list[Profile],
                 pods_fn: Callable[[], list[PodInfo]],
                 interval_seconds: float = 120.0, clock=time.time,
                 elector=None):
        self.profiles = profiles
        self.pods_fn = pods_fn
        self.interval_seconds = interval_seconds
        self.clock = clock
        #: optional ha.LeaderElector — the reference leader-elects the
        #: descheduler binary; a non-leader replica ticks but never evicts
        self.elector = elector
        self._last_run = 0.0

    def run_once(self) -> dict[str, int]:
        """One descheduling round; returns evictions per profile."""
        out = {}
        for profile in self.profiles:
            handle = _ProfileHandle(profile, self.pods_fn)
            for plugin in profile.deschedule_plugins:
                plugin.deschedule(handle)
            for plugin in profile.balance_plugins:
                plugin.balance(handle)
            out[profile.name] = handle.evictions
        return out

    def tick(self) -> Optional[dict[str, int]]:
        if self.elector is not None and not self.elector.tick():
            return None
        now = self.clock()
        if now - self._last_run < self.interval_seconds:
            return None
        self._last_run = now
        return self.run_once()
