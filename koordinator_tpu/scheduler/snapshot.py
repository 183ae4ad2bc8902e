"""Incremental cluster snapshot: informer deltas -> device tensors.

The reference scheduler snapshots its node cache every cycle (upstream
snapshotting model, SURVEY.md section 5 "race detection"); the TPU rebuild keeps the
cluster resident on device and applies *deltas*: the host maintains
name -> row maps and dirty-row buffers, and ``flush()`` ships only changed rows
(``ClusterState.scatter_update``). Capacity grows by power-of-two buckets so
recompilation is O(log N) over cluster life (SURVEY.md section 7 hard part (a)/(b)).

Request accounting (Reserve / Unreserve) is deferred the same way: a call
adds its signed vector to a host-side pending delta, and the next read of
``ClusterSnapshot.state`` folds everything pending into ``node_requested``
in one device op (``ClusterState.fold_requested``).

The GPU device plane rides the same two paths (``attach_devices``): the
attached ``DeviceManager``'s books are host numpy in these node rows, the
flush ships the rows an inventory event rewrote into
``ClusterState.devices``, and the fold adds what releases and commit-time
grants changed in its ``free``.  The CPU plane does the same
(``attach_cpus``): the ``CPUManager``'s table, ``ClusterState.cpus``, and
what releases and commit-time takes changed in its ``held``.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from koordinator_tpu import metrics, timeline
from koordinator_tpu.api.resources import NUM_RESOURCE_DIMS
from koordinator_tpu.state.cluster_state import ClusterState, _bucket

import jax
import jax.numpy as jnp

#: the device plane's two device ops: rows of an inventory tensor set
#: (donating, as the flush's node rows are) and the free delta added
_row_set = jax.jit(lambda cur, rows, value: cur.at[rows].set(value),
                   donate_argnums=(0,))
_free_fold = jax.jit(lambda cur, delta: cur + delta, donate_argnums=(1,))


@dataclasses.dataclass
class NodeSpec:
    """Host-side node record (what the Node informer + NodeMetric deliver)."""

    name: str
    allocatable: np.ndarray                 # (R,) int32
    usage: np.ndarray | None = None         # (R,) int32
    agg_usage: np.ndarray | None = None     # (R,) int32
    prod_usage: np.ndarray | None = None    # (R,) int32
    labels: dict[str, str] = dataclasses.field(default_factory=dict)
    #: NoSchedule taints as key -> value (a pod needs a matching toleration)
    taints: dict[str, str] = dataclasses.field(default_factory=dict)

    def signature(self) -> tuple:
        """Label/taint equivalence-class signature: nodes with equal
        signatures are interchangeable for selector/toleration filtering."""
        return (
            tuple(sorted(self.labels.items())),
            tuple(sorted(self.taints.items())),
        )


@dataclasses.dataclass
class PodSpec:
    """Host-side pending pod (what the webhook-mutated Pod object carries)."""

    name: str
    requests: np.ndarray                    # (R,) int32
    priority: int = 0
    qos: int = 0
    gang: str | None = None
    quota: str | None = None
    non_preemptible: bool = False
    node_selector: dict[str, str] = dataclasses.field(default_factory=dict)
    #: tolerated NoSchedule taints (key -> value)
    tolerations: dict[str, str] = dataclasses.field(default_factory=dict)
    creation: float = 0.0
    labels: dict[str, str] = dataclasses.field(default_factory=dict)
    owner: str | None = None               # controller key for reservation owner match
    #: pod.spec.preemptionPolicy — "Never" opts out of preempting others
    #: (PodEligibleToPreemptOthers, elasticquota/preempt.go:62)
    preemption_policy: str = "PreemptLowerPriority"
    #: manager-side ingest wall-clock (journey ledger, ISSUE 20); 0.0 when
    #: no stamp rode deltasync in.  Never read by solve or the pending
    #: sort key — that is `creation` — so it cannot perturb decisions.
    arrival_ts: float = 0.0
    #: a pinned (LSR / LSE) pod's resource-spec (``ops/numa`` codes,
    #: ``cpu_manager.parse_resource_spec``); without one the default bind
    #: policy at PCPU-level exclusivity
    cpu_bind_policy: int = 0           # BIND_DEFAULT
    cpu_exclusive_policy: int = 1      # EXCLUSIVE_PCPU_LEVEL


class ClusterSnapshot:
    """Name-indexed view over the device-resident ClusterState.

    The spec side (allocatable, usage, validity, class) is host-pending
    in ``_dirty`` until ``flush()``.  The request accounting is
    host-pending in ``_pending``: ``reserve`` / ``unreserve`` /
    ``unreserve_instance`` / ``reserve_batch`` touch no device, and
    reading ``state`` folds what they accumulated into
    ``node_requested`` first, so no reader ever sees a state that lacks
    a delta.  Integer adds commute: ``node_requested`` at every read is
    bit for bit what one device op per call would have left.

    A read may therefore write: every read of ``state`` and every call
    here is under the owning scheduler's lock.  A thread without it
    (a debug scrape) reads ``resident_state``, which folds nothing.
    The fold leaves the pre-fold buffers alive, so a state taken
    earlier and held across a later reserve stays readable, and stale,
    exactly as it did when each call was a device op of its own.
    """

    def __init__(self, capacity: int = 64, dims: int = NUM_RESOURCE_DIMS):
        self.dims = dims
        self._state = ClusterState.zeros(capacity, dims)
        #: (N, R) int32 sum of the Reserve / Unreserve vectors taken since
        #: the last fold, by row; None when nothing is pending (the read
        #: path's whole cost then).  ``_pending_calls`` counts, by row,
        #: the calls summed in it, so a row that dies takes its count
        #: along and a fold reports only what reached the device.
        self._pending: np.ndarray | None = None
        self._pending_calls: dict[int, int] = {}
        self.node_index: dict[str, int] = {}
        self._row_to_name: dict[int, str] = {}
        self.node_specs: dict[str, NodeSpec] = {}
        #: bumped whenever a row gets a node or loses it, or a node's
        #: labels change: what a tree built from labels keys its rebuild on
        self.labels_rev = 0
        self._free_rows: list[int] = list(range(capacity - 1, -1, -1))
        self._dirty: set[int] = set()
        #: rows whose solver-visible state changed since the incremental
        #: candidate cache last consumed them (superset of _dirty: spec
        #: upserts AND accounting changes — reserve/unreserve/solve
        #: adoption — land here; _dirty only tracks host-spec rows
        #: pending a device flush).  The scheduler's candidate cache
        #: derives its dirty-node column mask from this set.
        self._cand_dirty: set[int] = set()
        # rows whose solver-accumulated node_requested must be zeroed at next
        # flush (freed by remove_node; a reused row must not inherit the dead
        # node's accounting)
        self._reset_requested: set[int] = set()
        #: per-name INSTANCE counter, bumped each time a name (re)appears
        #: with a fresh row: a pod bound to the previous instance of a
        #: removed-then-readded node must not decrement the new one
        #: (re-add starts clean — see _reset_requested above)
        self.node_generation: dict[str, int] = {}
        # label/taint equivalence classes: signature -> class id. Ids are
        # never recycled (bounded by distinct signatures ever seen); the
        # (P, C) selector masks index them via ClusterState.node_class.
        self._class_index: dict[tuple, int] = {}
        self._class_sigs: list[tuple] = []
        #: clock time of the last applied sync event (delta/heartbeat)
        #: from whatever informer feeds this snapshot; None until the
        #: feed first speaks.  The scheduler's staleness watchdog reads
        #: the AGE of this stamp — a stalled feed means every usage- and
        #: batch-allocatable-derived row here is untrustworthy.
        self.last_sync_time: float | None = None
        #: where the solver wants the state (``SolverKit.place``): applied
        #: to every state built here, so a solve donates it in place
        #: instead of resharding it per call
        self._place = lambda state: state
        #: the attached ``DeviceManager`` (``attach_devices``) and which
        #: of its tables (identity, ``shape_rev``) ``_state.devices`` copies
        self.device_manager = None
        self._dev_seen: tuple = (None, 0)
        #: the attached ``CPUManager`` (``attach_cpus``) and which table
        #: ``_state.cpus`` copies, the same way
        self.cpu_manager = None
        self._cpu_seen: tuple = (None, 0)

    def attach_devices(self, manager) -> None:
        """Keep ``manager``'s books in this snapshot's node rows and its
        GPU table's device-resident copy in ``state.devices``."""
        self.device_manager = manager
        manager.attach(self)

    def attach_cpus(self, manager) -> None:
        """Keep ``manager``'s books in this snapshot's node rows and its
        table's device-resident copy in ``state.cpus``."""
        self.cpu_manager = manager
        manager.attach(self)

    def set_state_placement(self, place) -> None:
        """Install ``place(state) -> state`` and apply it to the state
        that stands; growth and the conservative rebuild apply it to
        theirs."""
        self._place = place
        self._state = place(self._state)

    def mark_sync(self, now: float) -> None:
        """Stamp feed liveness (monotonic under the writer's clock)."""
        self.last_sync_time = now

    def staleness(self, now: float) -> float | None:
        """Seconds since the feed last spoke; None before first contact."""
        if self.last_sync_time is None:
            return None
        return max(0.0, now - self.last_sync_time)

    @property
    def class_capacity(self) -> int:
        """Padded equivalence-class count for (P, C) selector masks."""
        return _bucket(max(len(self._class_sigs), 1), minimum=8)

    @property
    def class_count(self) -> int:
        """Registered equivalence classes (monotonic — ids never recycle);
        cache keys use this, not class_capacity, so a new class within the
        same padding bucket still invalidates."""
        return len(self._class_sigs)

    def _class_of(self, spec: NodeSpec) -> int:
        sig = spec.signature()
        cid = self._class_index.get(sig)
        if cid is None:
            cid = len(self._class_sigs)
            self._class_index[sig] = cid
            self._class_sigs.append(sig)
        return cid

    @staticmethod
    def _pod_allows(pod: PodSpec, labels: tuple, taints: tuple) -> bool:
        lbl = dict(labels)
        if any(lbl.get(k) != v for k, v in pod.node_selector.items()):
            return False
        return all(pod.tolerations.get(k) == v for k, v in taints)

    def selector_row_for(self, pod: PodSpec) -> np.ndarray:
        """(class_capacity,) bool: which node equivalence classes the pod's
        nodeSelector + tolerations admit. O(C) per pod — the factored
        replacement for the O(N) feasibility_row walk."""
        row = np.zeros(self.class_capacity, bool)
        for cid, (labels, taints) in enumerate(self._class_sigs):
            row[cid] = self._pod_allows(pod, labels, taints)
        return row

    @property
    def capacity(self) -> int:
        return self._state.capacity

    @property
    def state(self) -> ClusterState:
        """The device state with every Reserve / Unreserve folded in.
        Caller holds the owning scheduler's lock."""
        if self._pending is not None:
            self._fold()
        if self._state.devices is not None:
            self._fold_devices()
        if self._state.cpus is not None:
            self._fold_cpus()
        return self._state

    def _fold_cpus(self) -> None:
        """Add what releases, commit-time takes and topology rewrites
        changed in the CPU plane's ``held`` since the last read."""
        table = self.cpu_manager.solve_table()
        if table is None or (table, table.shape_rev) != self._cpu_seen:
            return   # the next flush rebuilds the copy from the books
        delta = table.take_pending()
        if delta is None:
            return
        with timeline.RECORDER.section("host_other", "snapshot.fold_cpus"):
            cpus = self._state.cpus
            placed = jax.device_put(delta, cpus.held.sharding)
            self._state = self._state.replace(
                cpus=cpus.replace(held=_free_fold(cpus.held, placed)))

    def _fold_devices(self) -> None:
        """Add what releases, commit-time grants and inventory rewrites
        changed in the device plane's ``free`` since the last read."""
        table = self.device_manager.solve_table()
        if table is None or (table, table.shape_rev) != self._dev_seen:
            return   # the next flush rebuilds the copy from the books
        delta = table.take_pending()
        if delta is None:
            return
        with timeline.RECORDER.section("host_other", "snapshot.fold_devices"):
            dev = self._state.devices
            placed = jax.device_put(delta, dev.free.sharding)
            self._state = self._state.replace(
                devices=dev.replace(free=_free_fold(dev.free, placed)))

    @property
    def resident_state(self) -> ClusterState:
        """The device tensors as they stand, nothing folded and nothing
        written: for readers of metadata (shapes, byte sizes, shardings)
        that do not hold the scheduler's lock.  Its ``node_requested``
        may lack what is pending, and its buffers may be donated to a
        solve in flight."""
        return self._state

    @state.setter
    def state(self, state: ClusterState) -> None:
        # what is pending stays pending and folds into the NEW state: a
        # delta taken while a solve is in flight lands on its result
        self._state = state

    def _fold(self) -> None:
        delta, n = self._pending, sum(self._pending_calls.values())
        with timeline.RECORDER.section("host_other", "snapshot.fold", n=n):
            self._state = self._state.fold_requested(delta)
        # the transfer owns ``delta`` now: the next delta gets a fresh array
        self._drop_pending()
        metrics.snapshot_requested_folds.inc()
        metrics.snapshot_requested_deltas_folded.inc(n)

    def _drop_pending(self) -> None:
        self._pending = None
        self._pending_calls.clear()

    def _defer(self, row: int, requests: np.ndarray, sign: int) -> None:
        """Add one signed request vector to the row's pending delta."""
        if self._pending is None:
            self._pending = np.zeros(self._state.node_requested.shape,
                                     np.int32)
        self._pending[row] += sign * np.asarray(requests, np.int32)
        self._pending_calls[row] = self._pending_calls.get(row, 0) + 1
        self._cand_dirty.add(row)

    def state_buffers_deleted(self) -> bool:
        """Has a donated-then-failed solve consumed the state's buffers?
        Probes the resident state: a fold into deleted buffers would
        raise."""
        return any(leaf.is_deleted()
                   for leaf in jax.tree.leaves(self._state))

    # -- node lifecycle -----------------------------------------------------

    def upsert_node(self, spec: NodeSpec) -> int:
        row = self.node_index.get(spec.name)
        if row is None:
            if not self._free_rows:
                self._grow()
            row = self._free_rows.pop()
            if row in self._reset_requested:
                # a freed row reused BEFORE the pending flush: zero the
                # dead node's accumulated requested NOW — deferring to
                # flush would also wipe any charge made against the new
                # instance in between (e.g. a pinned reservation's
                # make_available, a cross-scheduler nomination), whose
                # later generation-checked release would then drive
                # node_requested negative
                # (the row's pending delta died with the node)
                self._reset_requested.discard(row)
                self._state = self._state.replace(
                    node_requested=self._state.node_requested.at[row].set(0))
            self.node_index[spec.name] = row
            self._row_to_name[row] = spec.name
            self.node_generation[spec.name] = (
                self.node_generation.get(spec.name, -1) + 1)
            if self.device_manager is not None:
                self.device_manager.node_row_added(spec.name)
            if self.cpu_manager is not None:
                self.cpu_manager.node_row_added(spec.name)
        old = self.node_specs.get(spec.name)
        if old is None or old.labels != spec.labels:
            self.labels_rev += 1
        self.node_specs[spec.name] = spec
        self._class_of(spec)  # register the equivalence class up front
        self._dirty.add(row)
        self._cand_dirty.add(row)
        return row

    def remove_node(self, name: str) -> None:
        row = self.node_index.pop(name, None)
        if row is None:
            return
        del self.node_specs[name]
        del self._row_to_name[row]
        self.labels_rev += 1
        self._free_rows.append(row)
        self._dirty.add(row)
        self._cand_dirty.add(row)
        self._reset_requested.add(row)
        if self.device_manager is not None:
            # the row's next tenant must not inherit the devices
            self.device_manager.node_row_removed(row)
        if self.cpu_manager is not None:
            self.cpu_manager.node_row_removed(row)
        if self._pending_calls.pop(row, 0):
            # a delta taken against the dead instance must not land on
            # the row's next tenant: it goes with the row's accounting,
            # which the reset above zeroes, and with nothing else
            # pending there is nothing left to fold
            self._pending[row] = 0
            if not self._pending_calls:
                self._pending = None

    def _grow(self) -> None:
        old_cap = self.capacity
        new_cap = _bucket(old_cap + 1)
        old = self._state

        def pad(a):
            out = np.zeros((new_cap,) + a.shape[1:], a.dtype)
            out[:old_cap] = np.asarray(a)
            return jnp.asarray(out)

        self._state = ClusterState(
            node_allocatable=pad(old.node_allocatable),
            node_requested=pad(old.node_requested),
            node_usage=pad(old.node_usage),
            node_agg_usage=pad(old.node_agg_usage),
            node_prod_usage=pad(old.node_prod_usage),
            node_valid=pad(old.node_valid),
            node_class=pad(old.node_class),
        )
        if self.device_manager is not None:
            # the books grow with the rows; the next flush copies them
            self.device_manager.resize(new_cap)
            self._dev_seen = (None, 0)
        if self.cpu_manager is not None:
            self.cpu_manager.resize(new_cap)
            self._cpu_seen = (None, 0)
        if self._pending is not None:
            grown = np.zeros((new_cap,) + self._pending.shape[1:], np.int32)
            grown[:old_cap] = self._pending
            self._pending = grown
        self._free_rows = list(range(new_cap - 1, old_cap - 1, -1)) + self._free_rows
        self._state = self._place(self._state)

    # -- delta flush ---------------------------------------------------------

    def flush(self) -> int:
        """Ship dirty rows to device in one scatter. Returns rows shipped."""
        if self.device_manager is not None:
            self._flush_devices()
        if self.cpu_manager is not None:
            self._flush_cpus()
        if not self._dirty:
            return 0
        rows = sorted(self._dirty)
        with timeline.RECORDER.section("host_other", "snapshot.flush",
                                       n=len(rows)):
            self._flush_rows(rows)
        return len(rows)

    def _flush_devices(self) -> None:
        """Bring ``state.devices`` up to the GPU table's inventory: the
        rows an inventory event rewrote, by row; the whole table when it
        is new or was re-allocated; None while no node has devices (a
        cluster without them then runs the programs it always ran)."""
        table = self.device_manager.solve_table()
        if table is None:
            if self._state.devices is not None:
                self._state = self._state.replace(devices=None)
            self._dev_seen = (None, 0)
            return
        if (table, table.shape_rev) != self._dev_seen:
            if table.shape[0] != self.capacity:
                raise ValueError("device table rows are not the snapshot's")
            table.dirty.clear()
            table.take_pending()       # the books' ``free`` holds it
            self._cand_dirty.update(self.node_index.values())
            self._state = self._place(
                self._state.replace(devices=table.device_state()))
            self._dev_seen = (table, table.shape_rev)
            return
        if not table.dirty:
            return
        rows = np.asarray(sorted(table.dirty), np.int32)
        table.dirty.clear()
        self._cand_dirty.update(int(r) for r in rows)
        # ``free`` is not sent: what a rewrite changed in it is in the
        # table's pending delta, which adds up with a solve in flight
        idx = jnp.asarray(rows)
        dev = self._state.devices
        self._state = self._state.replace(devices=dev.replace(
            total=_row_set(dev.total, idx, jnp.asarray(table.total[rows])),
            valid=_row_set(dev.valid, idx, jnp.asarray(table.valid[rows])),
            healthy=_row_set(dev.healthy, idx,
                             jnp.asarray(table.healthy[rows])),
            group=_row_set(dev.group, idx, jnp.asarray(table.group[rows]))))

    def _flush_cpus(self) -> None:
        """Bring ``state.cpus`` up to the CPU table's topologies: the rows
        a topology event rewrote, by row; the whole table when it is new
        or was re-allocated; None while no node has a topology."""
        table = self.cpu_manager.solve_table()
        if table is None:
            if self._state.cpus is not None:
                self._state = self._state.replace(cpus=None)
            self._cpu_seen = (None, 0)
            return
        if (table, table.shape_rev) != self._cpu_seen:
            if table.shape[0] != self.capacity:
                raise ValueError("cpu table rows are not the snapshot's")
            table.dirty.clear()
            table.take_pending()       # the books' ``held`` has it
            self._cand_dirty.update(self.node_index.values())
            self._state = self._place(
                self._state.replace(cpus=table.cpu_state()))
            self._cpu_seen = (table, table.shape_rev)
            return
        if not table.dirty:
            return
        rows = np.asarray(sorted(table.dirty), np.int32)
        table.dirty.clear()
        self._cand_dirty.update(int(r) for r in rows)
        # ``held`` is not sent: what a rewrite changed in it is in the
        # table's pending delta, which adds up with a solve in flight
        idx = jnp.asarray(rows)
        cpus = self._state.cpus
        self._state = self._state.replace(cpus=cpus.replace(**{
            key: _row_set(getattr(cpus, key), idx,
                          jnp.asarray(getattr(table, key)[rows]))
            for key in ("core", "sib", "numa", "valid", "max_ref", "tpc",
                        "single_numa")}))

    def _flush_rows(self, rows: list[int]) -> None:
        self._dirty.clear()
        if self._reset_requested:
            reset = jnp.asarray(sorted(self._reset_requested), dtype=jnp.int32)
            self._reset_requested.clear()
            # rows freed and not reused: nothing is pending on them
            # (remove_node dropped it, and a row takes deltas again only
            # through upsert_node, which takes it out of this set)
            self._state = self._state.replace(
                node_requested=self._state.node_requested.at[reset].set(0)
            )
        k = len(rows)
        alloc = np.zeros((k, self.dims), np.int32)
        usage = np.zeros((k, self.dims), np.int32)
        agg = np.zeros((k, self.dims), np.int32)
        prod = np.zeros((k, self.dims), np.int32)
        valid = np.zeros(k, bool)
        nclass = np.zeros(k, np.int32)
        for i, r in enumerate(rows):
            name = self._row_to_name.get(r)
            if name is None:
                continue  # removed node: stays zero/invalid
            spec = self.node_specs[name]
            alloc[i] = spec.allocatable
            if spec.usage is not None:
                usage[i] = spec.usage
            agg[i] = spec.agg_usage if spec.agg_usage is not None else usage[i]
            prod[i] = spec.prod_usage if spec.prod_usage is not None else usage[i]
            valid[i] = True
            nclass[i] = self._class_of(spec)
        idx = jnp.asarray(np.asarray(rows, np.int32))
        # donate=True: the snapshot owns its state exclusively, so the
        # (N, R) tensors update in place instead of reallocating per flush
        self._state = self._state.scatter_update(
            idx,
            donate=True,
            node_allocatable=jnp.asarray(alloc),
            node_usage=jnp.asarray(usage),
            node_agg_usage=jnp.asarray(agg),
            node_prod_usage=jnp.asarray(prod),
            node_valid=jnp.asarray(valid),
            node_class=jnp.asarray(nclass),
        )

    # -- accounting ---------------------------------------------------------

    def reserve(self, node: str, requests: np.ndarray) -> None:
        """Account a binding onto a node (Reserve): host-pending until
        the next read of ``state``."""
        self._defer(self.node_index[node], requests, 1)

    def reserve_batch(self, requests_by_node) -> None:
        """Account many bindings (startup informer replay, warm-restart
        checkpoint restore), all or none: every node is resolved before
        the first vector is taken, so an unknown name raises KeyError
        with nothing accounted.  Otherwise the accumulation of
        :meth:`reserve`: a restore of any size is one fold at the next
        read, which is what makes it cheaper than re-placing the same
        pods through rounds."""
        rows = [self.node_index[node] for node in requests_by_node]
        for row, requests in zip(rows, requests_by_node.values()):
            self._defer(row, requests, 1)

    def unreserve(self, node: str, requests: np.ndarray) -> None:
        self._defer(self.node_index[node], requests, -1)

    def unreserve_instance(self, node: str, requests: np.ndarray,
                           generation: int) -> None:
        """Release a charge made against a SPECIFIC node instance: a
        no-op when the node is gone or the name now labels a fresh
        instance (re-add starts clean — decrementing it would drive
        node_requested negative).  Every release whose record can
        outlive the node (bound pods, nominations, reservation
        remainders) must come through here."""
        if node not in self.node_index:
            return
        if self.node_generation.get(node, 0) != generation:
            return
        self.unreserve(node, requests)

    def adopt_state(self, state: ClusterState,
                    changed_rows=None) -> None:
        """Adopt solver-updated accounting (post gang/greedy assign).

        ``changed_rows`` names the node rows whose ``node_requested`` the
        solver touched (the assigned rows) so the candidate cache only
        invalidates those; None is the conservative default — every
        valid row is treated as dirty."""
        if state.capacity != self.capacity:
            raise ValueError("state capacity mismatch")
        if changed_rows is None:
            self._cand_dirty.update(self.node_index.values())
        else:
            self._cand_dirty.update(int(r) for r in changed_rows)
        self._state = state

    def rebuild_conservative(self) -> None:
        """Disaster recovery for a DONATED-then-failed device state: a
        jitted solve that fails at execution time has already consumed
        the old buffers, so the accounting tensor (node_requested) is
        unrecoverable host-side.  Rebuild the spec-side tensors from
        ``node_specs`` and mark every valid node FULLY BOOKED
        (requested = allocatable): the scheduler keeps running and never
        overcommits, but places nothing new on existing nodes until a
        sync resync (SchedulerBinding.reset + bootstrap) or node churn
        restores exact accounting.  Releases stay safe: true bookings
        are always <= allocatable, so subtracting a released pod keeps
        the conservative row >= the true remaining bookings.  What was
        pending goes with the lost tensor: a reserve is covered by the
        full booking, and a release dropped only leaves a row fuller."""
        self._drop_pending()
        self._state = self._place(
            ClusterState.zeros(self.capacity, self.dims))
        # the device plane loses nothing: its books are on the host, and
        # the flush below copies them whole
        self._dev_seen = (None, 0)
        self._cpu_seen = (None, 0)
        self._reset_requested.clear()
        self._dirty.update(self.node_index.values())
        self._cand_dirty.update(self.node_index.values())
        self.flush()
        self._state = self._state.replace(
            node_requested=jnp.where(self._state.node_valid[:, None],
                                     self._state.node_allocatable,
                                     0))

    def consume_candidate_dirty(self) -> list[int]:
        """Rows dirtied since the last consume (sorted), clearing the set
        — called exactly when the candidate cache is rebuilt/refreshed."""
        rows = sorted(self._cand_dirty)
        self._cand_dirty.clear()
        return rows

    # -- queries ------------------------------------------------------------

    def node_name(self, row: int) -> str | None:
        return self._row_to_name.get(row)

    def feasibility_row(self, pod: PodSpec) -> np.ndarray:
        """(N,) bool host-computed selector/toleration mask for one pod.

        The dense path — used where per-(pod, node) edits are needed
        (scheduling hints, topology pins); the hot path uses
        :meth:`selector_row_for` + ``ClusterState.node_class`` instead.
        """
        mask = np.zeros(self.capacity, bool)
        for name, row in self.node_index.items():
            spec = self.node_specs[name]
            mask[row] = self._pod_allows(
                pod, tuple(spec.labels.items()), tuple(spec.taints.items())
            )
        return mask
