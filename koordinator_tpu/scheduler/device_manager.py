"""Host-side device bookkeeping (deviceshare Reserve/Unreserve).

Counterpart of the reference's nodeDevice cache updates
(pkg/scheduler/plugins/deviceshare/device_cache.go) and the
``scheduling.koordinator.sh/device-allocated`` annotation emitted at PreBind
(apis/extension/device_share.go:32): tracks which device minors each pod
holds and renders the annotation payload for the node agent's GPU
env-inject hook.

The books are host numpy, one :class:`DeviceTable` per device type, in the
node rows of the ``ClusterSnapshot`` the manager is attached to (its own
rows until then, for a manager used alone).  An inventory event rewrites
ONE row from that node's inventory and the grants held on it; nothing is
rebuilt and no other node's state moves.  The GPU table is also what the
batched solve sees: the snapshot keeps a device-resident copy beside
``ClusterState`` (``ClusterState.devices``) and ships it the rows an
inventory event dirtied at its flush; what a release or a commit-time grant
changes in ``free`` reaches that copy as ONE additive delta at the next
read of ``snapshot.state`` (``DeviceTable.pending``), exactly as Reserve /
Unreserve reach ``node_requested``.  A grant the solve made is already on
the device-resident copy and is only written into the books
(:meth:`DeviceManager.record_grants`, one call a round).
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from koordinator_tpu.ops.deviceshare import (
    DEV_BINPACK,
    DEV_CORE,
    DEV_MEM,
    NUM_DEV_DIMS,
    DeviceState,
    split_request,
)
from koordinator_tpu.state.cluster_state import _bucket

#: the device type the batched solve carries (``ClusterState.devices``)
SOLVE_DEVICE_TYPE = "gpu"


@dataclasses.dataclass
class DeviceAllocation:
    pod: str
    node: str
    device_type: str
    minors: list[int]
    core: int         # per-device core charged
    memory: int       # per-device memory charged


class DeviceTable:
    """One device type across the cluster, host numpy, padded (N, D)."""

    def __init__(self, capacity: int, devices: int = 8):
        self.total = np.zeros((capacity, devices, NUM_DEV_DIMS), np.int32)
        self.free = np.zeros((capacity, devices, NUM_DEV_DIMS), np.int32)
        self.valid = np.zeros((capacity, devices), bool)
        self.healthy = np.zeros((capacity, devices), bool)
        self.group = np.zeros((capacity, devices), np.int32)
        #: rows with an inventory
        self.rows: set[int] = set()
        #: rows whose inventory a device-resident copy has yet to be sent
        self.dirty: set[int] = set()
        #: (N, D, 2) int32 change of ``free`` that copy lacks, or None
        self.pending: np.ndarray | None = None
        #: bumped when the arrays were re-allocated: a device-resident
        #: copy is then rebuilt whole, as for a table it has not seen
        self.shape_rev = 0

    @property
    def shape(self) -> tuple[int, int]:
        return self.valid.shape

    def resize(self, capacity: int, devices: int) -> None:
        n, d = self.shape
        if (capacity, devices) == (n, d):
            return

        def grown(a):
            out = np.zeros((capacity, devices) + a.shape[2:], a.dtype)
            out[:n, :d] = a
            return out

        self.total, self.free = grown(self.total), grown(self.free)
        self.valid, self.healthy = grown(self.valid), grown(self.healthy)
        self.group = grown(self.group)
        self.pending = None
        self.shape_rev += 1

    def _defer(self, row: int, delta: np.ndarray) -> None:
        if self.pending is None:
            self.pending = np.zeros(self.free.shape, np.int32)
        self.pending[row] += delta

    def take_pending(self) -> np.ndarray | None:
        pending, self.pending = self.pending, None
        return pending

    def set_row(self, row: int, devices: list[dict],
                held: np.ndarray) -> None:
        """Write one node's inventory; ``held`` is the (D, 2) amount the
        grants recorded on that node hold, minor by minor."""
        d = self.shape[1]
        total = np.zeros((d, NUM_DEV_DIMS), np.int32)
        valid = np.zeros(d, bool)
        healthy = np.zeros(d, bool)
        group = np.zeros(d, np.int32)
        for j, dev in enumerate(devices):
            total[j, DEV_CORE] = dev.get("core", 100)
            total[j, DEV_MEM] = dev.get("memory", 0)
            valid[j] = True
            healthy[j] = dev.get("healthy", True)
            group[j] = dev.get("group", 0)
        free = np.where(valid[:, None], total - held, 0).astype(np.int32)
        self._defer(row, free - self.free[row])
        self.total[row], self.free[row] = total, free
        self.valid[row], self.healthy[row] = valid, healthy
        self.group[row] = group
        self.dirty.add(row)
        if devices:
            self.rows.add(row)
        else:
            self.rows.discard(row)

    def clear_row(self, row: int) -> None:
        if row in self.rows or self.valid[row].any():
            self.set_row(row, [], np.zeros((self.shape[1], NUM_DEV_DIMS),
                                           np.int32))

    def change_free(self, row: int, minors: list[int], core: int,
                    memory: int, sign: int) -> None:
        """Take (``sign`` -1) or give back (+1) a per-device amount on
        some minors of a row."""
        delta = np.zeros((self.shape[1], NUM_DEV_DIMS), np.int32)
        delta[minors] = (sign * core, sign * memory)
        self.free[row] += delta
        self._defer(row, delta)

    def grant(self, row: int, core: int, memory: int,
              strategy: int = DEV_BINPACK) -> list[int] | None:
        """DeviceShare Reserve on one row, by ``ops/deviceshare``'s rule
        (``allocate_on_node`` with no preferred group): the minors, or
        None when nothing fits."""
        n_whole, core, memory = split_request(core, memory)
        free, total = self.free[row], self.total[row]
        usable = self.valid[row] & self.healthy[row]
        minors = np.arange(self.shape[1])
        if n_whole == 0:
            fits = (usable & (free[:, DEV_CORE] >= core)
                    & (free[:, DEV_MEM] >= memory))
            if not fits.any():
                return None
            by_core = (free[:, DEV_CORE] if strategy == DEV_BINPACK
                       else -free[:, DEV_CORE])
            best = min(minors[fits], key=lambda m: (by_core[m], m))
            return [int(best)]
        wfree = (usable & (free == total).all(axis=-1)
                 & (total[:, DEV_CORE] >= core) & (total[:, DEV_MEM] >= memory))
        if int(wfree.sum()) < n_whole:
            return None
        group = self.group[row]
        count = np.array([int((wfree & (group == group[m])).sum())
                          for m in minors])
        leftover = np.where(count >= n_whole, count, np.iinfo(np.int32).max)
        ranked = sorted(minors[wfree], key=lambda m: (leftover[m], m))
        return sorted(int(m) for m in ranked[:n_whole])

    def device_state(self) -> DeviceState:
        # copies: the books keep changing in place under a buffer that
        # ``jnp.asarray`` may share with them on the CPU backend
        return DeviceState(
            total=jnp.array(self.total), free=jnp.array(self.free),
            valid=jnp.array(self.valid), healthy=jnp.array(self.healthy),
            group=jnp.array(self.group))


class _OwnRows:
    """The row space of a manager no snapshot is attached to."""

    def __init__(self) -> None:
        self.node_index: dict[str, int] = {}
        self.capacity = 64
        self._free: list[int] = []

    def row_for(self, node: str) -> int:
        row = self.node_index.get(node)
        if row is None:
            row = self._free.pop() if self._free else len(self.node_index)
            self.node_index[node] = row
        return row

    def drop(self, node: str) -> None:
        row = self.node_index.pop(node, None)
        if row is not None:
            self._free.append(row)


class DeviceManager:
    """Per-type device tables + pod allocation records."""

    def __init__(self) -> None:
        self._tables: dict[str, DeviceTable] = {}
        self._own_rows = _OwnRows()
        #: the attached snapshot (its ``node_index`` and ``capacity``)
        self._rows = self._own_rows
        self._allocs: dict[tuple[str, str], list[DeviceAllocation]] = {}
        #: pods with records on a node, for the one-row rewrite
        self._node_pods: dict[str, set[str]] = {}
        #: raw per-node inventory as last reported, by type
        self._raw: dict[str, dict[str, list[dict]]] = {}

    # -- rows -----------------------------------------------------------------

    def attach(self, snapshot) -> None:
        """Move the books into ``snapshot``'s node rows (its capacity,
        its name -> row map) and keep them there: the snapshot reports
        every row it hands out or takes back."""
        self._rows = snapshot
        self._tables = {
            device_type: DeviceTable(snapshot.capacity, table.shape[1])
            for device_type, table in self._tables.items()}
        for device_type, raw in self._raw.items():
            for node in raw:
                self._write_row(device_type, node)

    def _row(self, node: str, create: bool = False) -> int | None:
        if create and self._rows is self._own_rows:
            row = self._own_rows.row_for(node)
            while row >= self._own_rows.capacity:
                self._own_rows.capacity *= 2
            return row
        return self._rows.node_index.get(node)

    def node_row_added(self, node: str) -> None:
        """The snapshot gave ``node`` a row: an inventory that arrived
        before the node (or outlived a flap of it) lands there now."""
        for device_type, raw in self._raw.items():
            if node in raw:
                self._write_row(device_type, node)

    def node_row_removed(self, row: int) -> None:
        for table in self._tables.values():
            if row < table.shape[0]:
                table.clear_row(row)

    def resize(self, capacity: int) -> None:
        for table in self._tables.values():
            table.resize(capacity, table.shape[1])

    def solve_table(self) -> DeviceTable | None:
        """The table the batched solve carries, once a node has one."""
        table = self._tables.get(SOLVE_DEVICE_TYPE)
        return table if table is not None and table.rows else None

    def whole_free_devices(self, device_type: str = SOLVE_DEVICE_TYPE) -> int:
        """Usable devices of a type with nothing taken off them."""
        table = self._tables.get(device_type)
        if table is None:
            return 0
        return int((table.valid & table.healthy
                    & (table.free == table.total).all(axis=-1)).sum())

    # -- inventory ------------------------------------------------------------

    def _held(self, device_type: str, node: str, devices: int) -> np.ndarray:
        """(D, 2) amounts the recorded grants hold on a node's minors."""
        held = np.zeros((devices, NUM_DEV_DIMS), np.int32)
        for pod in self._node_pods.get(node, ()):
            for a in self._allocs.get((pod, node), ()):
                if a.device_type != device_type:
                    continue
                for m in a.minors:
                    if m < devices:
                        held[m] += (a.core, a.memory)
        return held

    def _write_row(self, device_type: str, node: str) -> None:
        """(Re)write ONE node's row from its raw inventory and the grants
        recorded on it: an inventory update cannot silently zero out held
        capacity, and touches no other node."""
        devices = self._raw.get(device_type, {}).get(node, [])
        row = self._row(node, create=bool(devices))
        if row is None:
            return   # the node has no row yet: ``node_row_added`` comes
        table = self._tables.get(device_type)
        if table is None:
            if not devices:
                return
            table = self._tables[device_type] = DeviceTable(
                self._rows.capacity, _bucket(len(devices), minimum=8))
        capacity = max(self._rows.capacity, table.shape[0])
        table.resize(capacity,
                     max(table.shape[1], _bucket(max(len(devices), 1),
                                                 minimum=8)))
        # a held minor the new inventory lacks is simply not valid: its
        # record stays (a transient clear must re-commit the grant when
        # the inventory returns) and is filtered from every view
        table.set_row(row, devices,
                      self._held(device_type, node, table.shape[1]))
        if not table.rows:
            # last node of the type gone: drop the type entirely
            del self._tables[device_type]

    def register(
        self, device_type: str, node_names: list[str], per_node_devices: list[list[dict]]
    ) -> None:
        for node in list(self._raw.get(device_type, {})):
            self.deregister_node_devices(device_type, node)
        for node, devices in zip(node_names, per_node_devices):
            self._raw.setdefault(device_type, {})[node] = list(devices)
            self._write_row(device_type, node)

    def register_node_devices(
        self, device_type: str, node: str, devices: list[dict]
    ) -> None:
        """Incremental Device-CR sync: (re)register one node's inventory."""
        raw = self._raw.setdefault(device_type, {})
        if raw.get(node) == list(devices):
            return   # unchanged heartbeat
        raw[node] = list(devices)
        self._write_row(device_type, node)

    def deregister_node_devices(self, device_type: str, node: str) -> None:
        """Remove one node's row for a type entirely (the type vanished
        from the node's full inventory).  POPPING rather than storing an
        empty list keeps live state identical to what bootstrap replay
        builds — a replayed doc without the type registers nothing, so
        the live side must hold nothing (tested by the randomized
        live-vs-replay parity suite)."""
        raw = self._raw.get(device_type)
        if raw is None or node not in raw:
            return
        raw.pop(node)
        if not raw:
            del self._raw[device_type]
        self._write_row(device_type, node)
        if self._rows is self._own_rows and not self.registered_types_for(node):
            self._own_rows.drop(node)

    def _live_minors(self, a: DeviceAllocation, row: int | None) -> list[int]:
        """The subset of a record's minors present in the CURRENT
        inventory.  Records are never pruned destructively: a transient
        inventory clear (a devices-omitting node re-upsert racing the
        koordlet heartbeat that repairs it) must re-commit the grant
        when the inventory returns; a minor that is really gone simply
        never re-commits and is filtered from annotations/release."""
        table = self._tables.get(a.device_type)
        if table is None or row is None or row >= table.shape[0]:
            return []
        return [m for m in a.minors
                if m < table.shape[1] and table.valid[row, m]]

    def remove_node(self, name: str) -> None:
        """Drop one node's inventory across all types (NODE_REMOVE).
        Allocation RECORDS stay: a node flap (NODE_REMOVE then re-upsert
        with devices, e.g. a kubelet restart while pods keep running)
        must re-commit held devices when the row is written again, or a
        second pod gets granted devices the first still uses — the same
        double-grant CPUManager.remove_node stashes orphans against.
        Records are purged when the pod itself is released (pod_remove
        reaches release()), so they are bounded by live pods."""
        for dev_type in list(self._raw):
            self.deregister_node_devices(dev_type, name)

    def registered_types_for(self, node: str) -> set[str]:
        """Device types this node has inventory registered under — lets
        a full-inventory refresh clear types that disappeared."""
        return {dev_type for dev_type, raw in self._raw.items()
                if node in raw}

    def clear(self) -> None:
        """Drop ALL inventory and allocation state — snapshot-resync
        restart semantics (SchedulerBinding.reset): types absent from the
        replayed snapshot must not survive as live allocatable tensors."""
        self._tables.clear()
        self._allocs.clear()
        self._node_pods.clear()
        self._raw.clear()
        if self._rows is self._own_rows:
            self._rows = self._own_rows = _OwnRows()

    def state(self, device_type: str) -> DeviceState | None:
        """The type's books as a ``DeviceState`` (a copy), or None."""
        table = self._tables.get(device_type)
        return table.device_state() if table is not None and table.rows else None

    # -- grants ---------------------------------------------------------------

    def _record(self, alloc: DeviceAllocation) -> None:
        self._allocs.setdefault((alloc.pod, alloc.node), []).append(alloc)
        self._node_pods.setdefault(alloc.node, set()).add(alloc.pod)

    def allocate(
        self,
        device_type: str,
        node: str,
        pod: str,
        core: int,
        memory: int = 0,
        strategy: int = DEV_BINPACK,
    ) -> list[int] | None:
        """Pick + commit devices for a pod on the host books; returns
        device minors or None.  The commit-time Reserve of every path the
        solve's device stage does not cover."""
        table = self._tables.get(device_type)
        row = self._row(node)
        if table is None or row is None or row not in table.rows:
            return None
        # Re-allocate for the same pod/type replaces the old grant (a retried
        # bind cycle must not double-charge); restore it if the retry fails.
        old = [a for a in self._allocs.get((pod, node), [])
               if a.device_type == device_type]
        for a in old:
            self._release_one(a)
            self._allocs[(pod, node)].remove(a)
        minors = table.grant(row, core, memory, strategy)
        if minors is None:
            for a in old:
                live = self._live_minors(a, row)
                table.change_free(row, live, a.core, a.memory, -1)
                self._record(a)
            return None
        _, per_core, per_mem = split_request(core, memory)
        table.change_free(row, minors, per_core, per_mem, -1)
        self._record(DeviceAllocation(pod, node, device_type, minors,
                                      per_core, per_mem))
        return minors

    def record_grants(self, grants: list[tuple[str, str, list[int], int, int]],
                      device_type: str = SOLVE_DEVICE_TYPE) -> None:
        """Write a round's solve-made grants into the books in one step:
        (pod, node, minors, per-device core, per-device memory) each.
        The device-resident copy already holds them."""
        table = self._tables[device_type]
        rows = np.array([self._row(node) for _, node, *_ in grants], np.intp)
        delta = np.zeros((len(grants),) + table.free.shape[1:], np.int32)
        for i, (pod, node, minors, core, memory) in enumerate(grants):
            delta[i, minors] = (core, memory)
            self._record(DeviceAllocation(pod, node, device_type, minors,
                                          core, memory))
        np.subtract.at(table.free, rows, delta)

    def _release_one(self, alloc: DeviceAllocation) -> None:
        table = self._tables.get(alloc.device_type)
        row = self._row(alloc.node)
        # only the live minors hold anything on the row, so only they
        # release — a dead minor in the record must not drive a
        # nonexistent device's free counter wrong
        live = self._live_minors(alloc, row)
        if live:
            table.change_free(row, live, alloc.core, alloc.memory, 1)

    def restore(self, node: str, pod: str, devices: dict) -> bool:
        """Replay a pod's existing device grants at startup from the
        device-allocated annotation payload
        ({type: [{"minor": m, "resources": {"core": c, "memory": b}}]}).
        Idempotent (a re-list that replays the same pod twice releases the
        previous records first) and defensive: annotation data is external,
        so unknown types and out-of-range minors are skipped rather than
        corrupting device accounting.  Returns True when anything landed."""
        self.release(node, pod)
        restored = False
        if not isinstance(devices, dict):
            return False
        row = self._row(node)
        for device_type, grants in devices.items():
            table = self._tables.get(device_type)
            if table is None or row is None or not isinstance(grants, list):
                continue
            for g in grants:
                try:
                    minor = int(g.get("minor", -1))
                    res = g.get("resources", {}) or {}
                    core = int(res.get("core", 0))
                    memory = int(res.get("memory", 0))
                except (TypeError, ValueError, AttributeError):
                    continue
                # bounds AND the row's valid mask: device capacities pad to
                # a power of two; a stale minor in the padding would drive
                # a nonexistent device's free counter negative
                if not (0 <= minor < table.shape[1]
                        and table.valid[row, minor]):
                    continue
                table.change_free(row, [minor], core, memory, -1)
                self._record(DeviceAllocation(pod, node, device_type,
                                              [minor], core, memory))
                restored = True
        return restored

    def release(self, node: str, pod: str) -> bool:
        """Unreserve: give back what ``pod`` holds on ``node``.  Host work
        alone; True when there was a record."""
        allocs = self._allocs.pop((pod, node), None)
        if allocs is None:
            return False
        for alloc in allocs:
            self._release_one(alloc)
        pods = self._node_pods.get(node)
        if pods is not None:
            pods.discard(pod)
            if not pods:
                del self._node_pods[node]
        return True

    def device_allocated_annotation(self, node: str, pod: str) -> dict | None:
        """The device-allocated annotation payload (device_share.go:32).
        Reports only minors present in the CURRENT inventory: records
        survive transient inventory clears undamaged, but a consumer
        (GPU env inject) must never see a device that is gone."""
        allocs = self._allocs.get((pod, node))
        if not allocs:
            return None
        out: dict = {}
        row = self._row(node)
        for a in allocs:
            for m in self._live_minors(a, row):
                out.setdefault(a.device_type, []).append(
                    {"minor": m,
                     "resources": {"core": a.core, "memory": a.memory}})
        return out or None
