"""The bound pods as columns: one flat view of ``Scheduler.bound``.

``Scheduler.bound`` stays the name -> :class:`BoundPod` registry every
path reads and writes; :class:`BoundRegistry` is that dict, and keeps a
:class:`BoundColumns` beside it in step with every insert, replace and
pop.  Two readers take the columns instead of walking the registry pod by
pod: preemption's victim table (``Scheduler._build_scheduled``) and the
descheduler's victim universe (``descheduler/plugins.py``).

Keeping in step costs the bind path one dict insert: a new pod waits in
``BoundColumns._unwritten`` until somebody reads the columns
(``BoundRegistry.columns`` writes the waiting pods first, one assignment per
column for the whole batch), so a scheduler whose columns nobody reads
never fills them.  A pod keeps one slot from its first read to its
release; freed slots are reused.  Names
that repeat across pods (node, namespace, workload, quota, label set) are
interned to small ints, so masks over them are numpy compares.  The node a
pod was charged to is held as (interned name, instance generation) and
resolved to the CURRENT row at read time (:meth:`BoundColumns.node_rows`):
a pod bound to a previous instance of a re-added node reads -1, as
``_build_scheduled`` always had it.

Per-pod usage has no wire kind: ``usage`` is a column the embedding
deployment sets (:meth:`BoundColumns.set_usage`); a pod none was set for
reads its request.
"""

from __future__ import annotations

import numpy as np

from koordinator_tpu.api import extension as ext

#: ``flags`` bits
NON_PREEMPTIBLE = 1
DAEMONSET = 2
LOCAL_STORAGE = 4
EVICT_FORBIDDEN = 8

#: the eviction-cost annotation value that forbids eviction outright
_COST_FORBIDS = "-2147483648"


def pod_namespace(name: str) -> str:
    """A pod key is ``namespace/name``; a bare name lives in ``default``."""
    head, sep, _ = name.partition("/")
    return head if sep else "default"


class Interner:
    """value -> small int, stable for the life of the columns."""

    def __init__(self) -> None:
        self.ids: dict = {}
        self.values: list = []

    def __call__(self, value) -> int:
        i = self.ids.get(value)
        if i is None:
            i = self.ids[value] = len(self.values)
            self.values.append(value)
        return i

    def __len__(self) -> int:
        return len(self.values)


class BoundColumns:
    """Column arrays over slots ``[0, size)``; ``live`` marks the slots
    that hold a pod now."""

    _INT_COLUMNS = ("node_id", "node_gen", "priority", "qos", "namespace_id",
                    "workload_id", "quota_id", "labelset_id", "flags")

    def __init__(self, dims: int, capacity: int = 1024):
        self.dims = dims
        self.size = 0
        #: bumped by every add and remove: a reader's cache key
        self.version = 0
        self.slot_of: dict[str, int] = {}
        #: bound, and not yet written into the columns: name -> pod
        self._unwritten: dict = {}
        self.names: list[str | None] = []
        self._free: list[int] = []
        self.nodes = Interner()
        self.namespaces = Interner()
        #: id 0 is "no owner" / "no quota" / "no labels"
        self.workloads = Interner()
        self.quotas = Interner()
        self.labelsets = Interner()
        for interner, none in ((self.workloads, ""), (self.quotas, None),
                               (self.labelsets, ())):
            interner(none)
        self.live = np.zeros(capacity, bool)
        self.usage_set = np.zeros(capacity, bool)
        self.requests = np.zeros((capacity, dims), np.int32)
        self.usage = np.zeros((capacity, dims), np.int32)
        for column in self._INT_COLUMNS:
            setattr(self, column, np.zeros(capacity, np.int32))

    def __len__(self) -> int:
        return len(self.slot_of) + len(self._unwritten)

    def _grow(self) -> None:
        for column in ("live", "usage_set", "requests", "usage",
                       *self._INT_COLUMNS):
            old = getattr(self, column)
            new = np.zeros((2 * len(old),) + old.shape[1:], old.dtype)
            new[: len(old)] = old
            setattr(self, column, new)

    # -- kept in step by BoundRegistry ---------------------------------------

    def _take_slot(self, name: str) -> int:
        if self._free:
            slot = self._free.pop()
        else:
            if self.size == len(self.live):
                self._grow()
            slot = self.size
            self.size += 1
            self.names.append(None)
        self.slot_of[name] = slot
        self.names[slot] = name
        return slot

    def _ints(self, pod) -> tuple:
        """One pod's row of ``_INT_COLUMNS``, names interned."""
        labels, owner = pod.labels, pod.owner or ""
        return (
            self.nodes(pod.node), pod.node_generation, pod.priority, pod.qos,
            self.namespaces(pod_namespace(pod.name)), self.workloads(owner),
            self.quotas(pod.quota),
            self.labelsets(tuple(sorted(labels.items())) if labels else ()),
            (NON_PREEMPTIBLE if pod.non_preemptible else 0)
            | (DAEMONSET if owner.startswith("DaemonSet/") else 0)
            | (LOCAL_STORAGE if pod.local_storage else 0)
            | (EVICT_FORBIDDEN if pod.annotations
               and eviction_forbidden(pod.annotations) else 0))

    def add(self, pod) -> None:
        self.version += 1
        self._unwritten[pod.name] = pod

    def write(self) -> None:
        """Write the pods bound since the last read into the columns."""
        pods = list(self._unwritten.values())
        if not pods:
            return
        self._unwritten.clear()
        slots = [self._take_slot(pod.name) for pod in pods]
        ints = np.array([self._ints(pod) for pod in pods], np.int64)
        for j, column in enumerate(self._INT_COLUMNS):
            getattr(self, column)[slots] = ints[:, j]
        self.requests[slots] = [pod.requests for pod in pods]
        self.live[slots] = True
        self.usage_set[slots] = False

    def remove(self, name: str) -> None:
        self.version += 1
        if self._unwritten.pop(name, None) is not None:
            return
        slot = self.slot_of.pop(name)
        self.names[slot] = None
        self.live[slot] = False
        self._free.append(slot)

    # -- the deployment's column ---------------------------------------------

    def set_usage(self, names, usage: np.ndarray) -> None:
        """``usage`` (k, R) for the bound pods ``names``; a name that is
        not bound is skipped."""
        usage = np.asarray(usage, np.int32)
        slot_of = self.slot_of
        slots = np.fromiter((slot_of.get(n, -1) for n in names), np.int64,
                            len(usage))
        known = slots >= 0
        self.usage[slots[known]] = usage[known]
        self.usage_set[slots[known]] = True

    # -- readers --------------------------------------------------------------

    def slots(self, names) -> np.ndarray:
        slot_of = self.slot_of
        return np.fromiter((slot_of[n] for n in names), np.int64, len(names))

    def node_rows(self, snapshot) -> np.ndarray:
        """(size,) the CURRENT snapshot row of each slot's node; -1 for a
        node that is gone, a previous instance of a re-added one, or a dead
        slot."""
        row = np.full(len(self.nodes), -1, np.int32)
        gen = np.full(len(self.nodes), -1, np.int32)
        index, generation = snapshot.node_index, snapshot.node_generation
        for i, name in enumerate(self.nodes.values):
            r = index.get(name)
            if r is not None:
                row[i] = r
                gen[i] = generation.get(name, 0)
        n = self.size
        ids = self.node_id[:n]
        return np.where(self.live[:n] & (gen[ids] == self.node_gen[:n]),
                        row[ids], -1).astype(np.int32)

    def pod_usage(self) -> np.ndarray:
        """(size, R) the usage column; the request where none was set."""
        n = self.size
        return np.where(self.usage_set[:n, None], self.usage[:n],
                        self.requests[:n])

    def labels_of(self, labelset: int) -> dict:
        return dict(self.labelsets.values[labelset])


def eviction_forbidden(annotations: dict | None) -> bool:
    return bool(annotations) and annotations.get(
        ext.ANNOTATION_EVICTION_COST, "") == _COST_FORBIDS


class BoundRegistry(dict):
    """``Scheduler.bound``: name -> BoundPod, with ``columns`` in step."""

    def __init__(self, dims: int):
        super().__init__()
        self._columns = BoundColumns(dims)

    @property
    def columns(self) -> BoundColumns:
        """The columns, every bound pod written."""
        self._columns.write()
        return self._columns

    def __setitem__(self, name: str, pod) -> None:
        if name in self:
            self._columns.remove(name)
        super().__setitem__(name, pod)
        self._columns.add(pod)

    def __delitem__(self, name: str) -> None:
        super().__delitem__(name)
        self._columns.remove(name)

    _MISSING = object()

    def pop(self, name: str, default=_MISSING):
        if name in self:
            self._columns.remove(name)
            return super().pop(name)
        if default is self._MISSING:
            raise KeyError(name)
        return default

    def popitem(self):
        name, pod = super().popitem()
        self._columns.remove(name)
        return name, pod

    def clear(self) -> None:
        for name in list(self):
            del self[name]

    def update(self, *args, **kwargs) -> None:
        for name, pod in dict(*args, **kwargs).items():
            self[name] = pod

    def setdefault(self, name: str, default=None):
        if name not in self:
            self[name] = default
        return self[name]
