"""Plain reference of koordinator's DeviceShare plugin for GPUs: the request
split, Filter, Reserve / Unreserve and a replay of a deployment's books.

int64 numpy and Python loops, one pod and one node at a time; imports
nothing of the program.  Written from memory of upstream
(``apis/extension/device_share.go``; ``pkg/scheduler/plugins/deviceshare/``
``device_cache.go``, ``device_allocator.go``, ``allocator_gpu.go``): there is
no network and no copy of the source on this machine.

The protocol: ``koordinator.sh/gpu-core`` in percent of one device (100 = a
whole GPU), ``koordinator.sh/gpu-memory`` in MiB.  A request of at most 100
core is SHARED: it lands on one device with that much core and memory free.
A request above 100 must be a multiple of 100 (``ValidateDeviceRequest``) and
takes n = core / 100 devices that are wholly free, each large enough for
core 100 and memory / n.

Departures from the published description, each on purpose:

- upstream's allocator scores candidate devices by the configured strategy
  and, for several devices, honours the node's PCIe / NUMA topology through
  its ``AutopilotAllocator``.  Here Reserve follows the PROGRAM's default
  rule (``DEV_BINPACK``), which is what a deployment with default arguments
  runs: shared = the fitting device with the least free core, ties to the
  lowest minor; whole = n wholly free devices taken from the topology group
  that can satisfy the ask with the fewest wholly free devices (least left
  over), groups that cannot satisfy it last, ties to the lowest minor.
- a request of exactly 100 core follows the shared path (one device with 100
  core free), as the program's ``split_request`` has it; upstream treats it
  the same way for a single device.
- an invalid request (above 100 and no multiple of 100) is refused here
  (``validate``); the program rounds it up to whole devices.  The deployment
  never draws one.
- RDMA, joint GPU + NIC allocation, partition tables and DeviceShare's Score
  are not modelled: the deployment does not exercise them.
"""

from __future__ import annotations

import numpy as np

CORE, MEM = 0, 1


def validate(core: int) -> bool:
    """``ValidateDeviceRequest``: above one device, whole devices only."""
    return core <= 100 or core % 100 == 0


def split_request(core: int, memory: int) -> tuple[int, int, int]:
    """(n whole devices or 0 for shared, per-device core, per-device memory)."""
    if core <= 100:
        return 0, core, memory
    n = -(-core // 100)
    return n, 100, -(-memory // n) if memory else 0


class Table:
    """Per-device state of ``nodes`` nodes x ``devices`` slots."""

    def __init__(self, nodes: int, devices: int):
        self.total = np.zeros((nodes, devices, 2), np.int64)
        self.free = np.zeros((nodes, devices, 2), np.int64)
        self.valid = np.zeros((nodes, devices), bool)
        self.healthy = np.zeros((nodes, devices), bool)
        self.group = np.zeros((nodes, devices), np.int64)

    def set_inventory(self, node: int, devices: list[dict]) -> None:
        """A node's Device CR as reported; what is granted on it stays
        granted (free = total less what the held grants take)."""
        held = self.total[node] - self.free[node]
        self.total[node] = 0
        self.valid[node] = self.healthy[node] = False
        self.group[node] = 0
        for minor, dev in enumerate(devices):
            self.total[node, minor] = (dev.get("core", 100),
                                       dev.get("memory", 0))
            self.valid[node, minor] = True
            self.healthy[node, minor] = dev.get("healthy", True)
            self.group[node, minor] = dev.get("group", 0)
        self.free[node] = np.where(self.valid[node][:, None],
                                   self.total[node] - held, 0)

    def usable(self, node: int) -> np.ndarray:
        return self.valid[node] & self.healthy[node]

    # -- Filter ---------------------------------------------------------------

    def node_fits(self, node: int, core: int, memory: int) -> bool:
        n, per_core, per_mem = split_request(core, memory)
        usable = self.usable(node)
        free, total = self.free[node], self.total[node]
        if n == 0:
            return bool(np.any(usable & (free[:, CORE] >= per_core)
                               & (free[:, MEM] >= per_mem)))
        whole = (usable & np.all(free == total, axis=1)
                 & (total[:, CORE] >= per_core) & (total[:, MEM] >= per_mem))
        return int(whole.sum()) >= n

    def filter(self, core: int, memory: int) -> np.ndarray:
        """(nodes,) bool: Filter of one pod over all nodes."""
        return np.array([self.node_fits(node, core, memory)
                         for node in range(self.valid.shape[0])], bool)

    # -- Reserve / Unreserve --------------------------------------------------

    def reserve(self, node: int, core: int, memory: int) -> list[int] | None:
        """The minors granted on ``node`` (taken off its devices), or None."""
        n, per_core, per_mem = split_request(core, memory)
        usable = self.usable(node)
        free, total = self.free[node], self.total[node]
        slots = range(self.valid.shape[1])
        if n == 0:
            fitting = [m for m in slots if usable[m]
                       and free[m, CORE] >= per_core and free[m, MEM] >= per_mem]
            if not fitting:
                return None
            minors = [min(fitting, key=lambda m: (free[m, CORE], m))]
        else:
            whole = [m for m in slots if usable[m]
                     and free[m, CORE] == total[m, CORE]
                     and free[m, MEM] == total[m, MEM]
                     and total[m, CORE] >= per_core
                     and total[m, MEM] >= per_mem]
            if len(whole) < n:
                return None
            in_group = {m: sum(1 for o in whole
                               if self.group[node, o] == self.group[node, m])
                        for m in whole}
            never = len(slots) + 1
            ranked = sorted(whole, key=lambda m: (
                in_group[m] if in_group[m] >= n else never, m))
            minors = sorted(ranked[:n])
        for m in minors:
            self.free[node, m] -= (per_core, per_mem)
        return minors

    def unreserve(self, node: int, minors: list[int], per_core: int,
                  per_mem: int) -> None:
        for m in minors:
            if self.valid[node, m]:
                self.free[node, m] += (per_core, per_mem)


def grant_faults(table: Table, node: int, minors: list[int], core: int,
                 memory: int, per_core: int, per_mem: int) -> int:
    """1 when a grant as the program reported it is not one Reserve could
    have made on ``node`` at that moment: a device that is not there, not
    usable, the wrong count, or a per-device amount other than the split's
    (or more than the device had free)."""
    n, want_core, want_mem = split_request(core, memory)
    if len(minors) != max(n, 1) or len(set(minors)) != len(minors):
        return 1
    if (per_core, per_mem) != (want_core, want_mem):
        return 1
    for m in minors:
        if not (0 <= m < table.valid.shape[1]) or not table.usable(node)[m]:
            return 1
        if table.free[node, m, CORE] < per_core or table.free[node, m, MEM] < per_mem:
            return 1
        if n and not np.array_equal(table.free[node, m], table.total[node, m]):
            return 1
    return 0


def replay(nodes: int, devices: int, events: list[tuple]) -> dict:
    """Rebuild the per-device table from a deployment's books, in order:

    - ``("inventory", node, [device dicts])``
    - ``("bind", pod, node, core, memory, grant)`` with ``grant`` the
      program's ``{"minor": m, "resources": {"core": c, "memory": b}}`` list,
      or None when it bound the pod with none
    - ``("leave", pod)``

    Returns the table and the counts a deployment compares: a bind of a
    device pod without a grant, a grant Reserve could not have made, and the
    (node, device, dim) cells whose grants ever summed above the device."""
    table = Table(nodes, devices)
    held: dict[str, tuple[int, list[int], int, int]] = {}
    bind_without_grant = grant_invalid = 0
    overcommitted: set[tuple[int, int, int]] = set()
    for event in events:
        if event[0] == "inventory":
            table.set_inventory(event[1], event[2])
        elif event[0] == "bind":
            _, pod, node, core, memory, grant = event
            if core <= 0 and memory <= 0:
                continue
            if not grant:
                bind_without_grant += 1
                continue
            minors = [int(g["minor"]) for g in grant]
            amounts = {(int(g["resources"]["core"]),
                        int(g["resources"]["memory"])) for g in grant}
            per_core, per_mem = (next(iter(amounts)) if len(amounts) == 1
                                 else (-1, -1))
            if grant_faults(table, node, minors, core, memory, per_core,
                            per_mem):
                grant_invalid += 1
            in_range = [m for m in minors if 0 <= m < devices]
            for m in in_range:
                table.free[node, m] -= (per_core, per_mem)
                for dim in (CORE, MEM):
                    if table.valid[node, m] and table.free[node, m, dim] < 0:
                        overcommitted.add((node, m, dim))
            held[pod] = (node, in_range, per_core, per_mem)
        elif event[0] == "leave":
            grant = held.pop(event[1], None)
            if grant is not None:
                table.unreserve(*grant)
    return {"table": table, "bind_without_grant": bind_without_grant,
            "grant_invalid": grant_invalid,
            "device_overcommit_cells": len(overcommitted)}
