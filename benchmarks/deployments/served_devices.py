"""A shared-GPU cluster behind the served scheduler: ``served_socket``'s
deployment (``koord-scheduler`` assembled by ``MAINS``, rounds on its listen
socket) with a device inventory on every node and jobs that ask for a share
of a GPU or for whole ones.

What this shell adds to ``served_socket.Deployment``:

- nodes arrive by ``upsert_node(..., devices={"gpu": [...]})`` and inventory
  changes by ``update_node_devices`` (the ``NODE_DEVICES`` kind), both through
  the sync service in process;
- it draws the job mix from the seed (the configuration's ``mix``), gives a
  bound pod a lifetime in cycles and a pending pod a patience in rounds, and
  after every round reads each new bind's ``device-allocated`` annotation from
  the scheduler's ``resource_status`` (what the embedding shell would write
  onto the pod at PreBind) into its books;
- ``verify()`` replays those books through the plain reference
  (``reference/deviceshare.py``) and compares the guarantees the
  configuration states, all at limit 0.
"""

from __future__ import annotations

import numpy as np

from benchmarks.deployments import served_socket
from benchmarks.reference import checks
from benchmarks.reference import deviceshare as reference


class Deployment(served_socket.Deployment):

    def __init__(self, config: dict, sizes: dict, seed: int, run_dir: str):
        from koordinator_tpu.ops import deviceshare

        if not hasattr(deviceshare, "DeviceGrants"):
            # a program whose solve knows no device binds these pods on
            # the node's aggregate rows; it must end here, at once
            raise SystemExit(
                "benchmarks/deployments/served_devices.py: this program's "
                "solve carries no device stage (ops/deviceshare.py has no "
                "DeviceGrants); the deployment cannot run on it")
        super().__init__(config, sizes, seed, run_dir)
        self.gpu = config["gpu"]
        self.mix = config["mix"]
        self.cycle_no = 0
        #: the books of the device plane, in order, for the replay
        self.events: list[tuple] = []
        self.inventory: dict[str, list[dict]] = {}
        #: minors this shell turned unhealthy, oldest first
        self.unhealthy: list[tuple[str, int]] = []
        self.leaves_at: dict[str, int] = {}      # bound pod -> cycle
        self.gives_up_at: dict[str, int] = {}    # pending pod -> cycle
        self.shape_of: dict[str, str] = {}
        self.grants: dict[str, list | None] = {}
        self.last_failures: dict[str, str] = {}
        self.last_bound: dict[str, str] = {}
        self.withdrawn_by_shape: dict[str, int] = {}
        self._standing_names: set[str] = set()

    # -- set-up -------------------------------------------------------------

    def load_nodes(self, replays: int = 1) -> None:
        if replays != 1:
            raise NotImplementedError("one assembled scheduler, one cluster")
        n, dims, gpu, rng = self.sizes["nodes"], self.dims, self.gpu, self.rng
        ranges = self.config["value_ranges"]
        alloc = np.zeros((n, dims["count"]), np.int32)
        alloc[:, dims["cpu"]] = rng.choice(ranges["node_cpu_milli"], n)
        alloc[:, dims["memory"]] = rng.choice(ranges["node_memory_mib"], n)
        per_node = np.full(n, gpu["per_node"])
        # the last node is the small one: half the devices
        per_node[-1] = gpu["small_node_devices"]
        alloc[:, dims["gpu"]] = per_node * gpu["core"]
        alloc[:, dims["gpu_memory"]] = per_node * gpu["memory_mib"]
        usage = np.zeros_like(alloc)
        for dim in ("cpu", "memory"):
            usage[:, dims[dim]] = (alloc[:, dims[dim]] * rng.random(n)
                                   * ranges["node_usage_share_max"])
        names = [f"n{i}" for i in range(n)]
        self.books.set_nodes(names, alloc, usage)
        sick = rng.random((n, gpu["per_node"])) < gpu["unhealthy_share"]
        self.hang_up()
        for i, name in enumerate(names):
            devices = [{"core": gpu["core"], "memory": gpu["memory_mib"],
                        "group": minor // gpu["group_size"],
                        "healthy": not sick[i, minor]}
                       for minor in range(per_node[i])]
            self.unhealthy += [(name, m) for m in range(per_node[i])
                               if sick[i, m]]
            self.inventory[name] = devices
            self.events.append(("inventory", i, [dict(d) for d in devices]))
            self.sync.upsert_node(name, alloc[i], usage=usage[i],
                                  devices={"gpu": devices})

    def standing(self, first: int = 0, last: int | None = None) -> list[tuple]:
        """Pods the node's aggregate rows hold and no device does: one GPU
        with more memory than a device has.  Never bound, always
        diagnosed by the device filter."""
        ask = self.mix["standing"]
        request = np.zeros(self.dims["count"], np.int32)
        request[self.dims["cpu"]] = self.mix["cpu_per_gpu"]
        request[self.dims["memory"]] = self.mix["memory_per_gpu"]
        request[self.dims["gpu"]] = ask["core"]
        request[self.dims["gpu_memory"]] = ask["gpu_memory_mib"]
        last = self.sizes["standing"] if last is None else last
        pods = [(f"whale{i}", request, 9_500, self.qos["LS"], {})
                for i in range(first, last)]
        self._standing_names.update(p[0] for p in pods)
        return pods

    def wave(self, n: int | None = None) -> list[tuple]:
        """``n`` jobs of the configuration's mix."""
        n = self.sizes["wave_pods"] if n is None else n
        mix, dims, gpu, rng = self.mix, self.dims, self.gpu, self.rng
        shapes = list(mix["shapes"])
        drawn = rng.choice(len(shapes), n,
                           p=[mix["shapes"][s]["share"] for s in shapes])
        requests = np.zeros((n, dims["count"]), np.int32)
        plain_cpu = rng.integers(*mix["plain_cpu_milli"], n, endpoint=True)
        plain_mem = rng.integers(*mix["plain_memory_mib"], n, endpoint=True)
        cores = rng.choice(mix["share_cores"], n)
        for i, which in enumerate(drawn):
            gpus = mix["shapes"][shapes[which]]["gpus"]
            if gpus == 0:
                requests[i, dims["cpu"]] = plain_cpu[i]
                requests[i, dims["memory"]] = plain_mem[i]
                continue
            core = int(cores[i]) if gpus == "share" else gpus * gpu["core"]
            requests[i, dims["gpu"]] = core
            requests[i, dims["gpu_memory"]] = gpu["memory_mib"] * core // gpu["core"]
            requests[i, dims["cpu"]] = max(
                mix["cpu_floor"], mix["cpu_per_gpu"] * core // gpu["core"])
            requests[i, dims["memory"]] = max(
                mix["memory_floor"],
                mix["memory_per_gpu"] * core // gpu["core"])
        priority = rng.integers(*mix["priority"], n)
        base = self.serial
        self.serial += n
        pods = []
        for i in range(n):
            name = f"p{base + i}"
            self.shape_of[name] = shapes[drawn[i]]
            pods.append((name, requests[i], int(priority[i]),
                         self.qos["LS"], {}))
        return pods

    def offer(self, pods: list[tuple], counts: bool = True) -> None:
        super().offer(pods, counts)
        for name, *_ in pods:
            if name not in self._standing_names:
                self.gives_up_at[name] = self.cycle_no + self.mix["patience"]

    def withdraw(self, pods: list[tuple]) -> None:
        """Only what is still pending: a standing pod that a faulty program
        bound stays in the books as bound, for ``standing_bound``."""
        super().withdraw([p for p in pods if p[0] in self.books.pending])

    # -- one cycle's steps --------------------------------------------------

    def depart(self) -> int:
        """The pods whose lifetime ends leave."""
        gone = [p for p, at in self.leaves_at.items() if at <= self.cycle_no]
        self.hang_up()
        for pod in gone:
            del self.leaves_at[pod]
            self.books.leave(pod)
            self.grants.pop(pod, None)
            self.events.append(("leave", pod))
            self.sync.remove_pod(pod)
        return len(gone)

    def give_up(self) -> int:
        """A pod still pending when its owner's patience ends is withdrawn:
        offered, and not bound."""
        gone = [p for p, at in self.gives_up_at.items()
                if at <= self.cycle_no and p in self.books.pending]
        by_shape = self.withdrawn_by_shape
        for pod in gone:
            del self.gives_up_at[pod]
            shape = self.shape_of.get(pod, "fill")
            by_shape[shape] = by_shape.get(shape, 0) + 1
        self.withdraw([(pod,) for pod in gone])
        return len(gone)

    def device_events(self, n: int) -> int:
        """``n`` Device-CR refreshes: a device on a node with pods running
        turns unhealthy, and the one that has been unhealthy longest
        recovers, alternately."""
        busy = sorted(set(self.books.bound.values()))
        sent = 0
        for k in range(n):
            if k % 2 == 0 and busy:
                node = busy[int(self.rng.integers(len(busy)))]
                well = [m for m, d in enumerate(self.inventory[node])
                        if d["healthy"]]
                if not well:
                    continue
                minor = well[int(self.rng.integers(len(well)))]
                self.unhealthy.append((node, minor))
                healthy = False
            elif self.unhealthy:
                node, minor = self.unhealthy.pop(0)
                healthy = True
            else:
                continue
            devices = [dict(d) for d in self.inventory[node]]
            devices[minor]["healthy"] = healthy
            self.inventory[node] = devices
            self.events.append(("inventory", self.books.node_row[node],
                                [dict(d) for d in devices]))
            self.hang_up()
            self.sync.update_node_devices(node, {"gpu": devices})
            sent += 1
        return sent

    def solve(self) -> int:
        """One round on the socket, then the new binds' grants into the
        books: what the scheduler wrote for PreBind."""
        from koordinator_tpu.transport.services import solve_remote

        doc = solve_remote(self.connected())
        bound = self.books.record_round(doc)
        self.last_failures = doc["failures"]
        self.last_bound = doc["assignments"]
        dims, lifetime = self.dims, self.mix["lifetime_cycles"]
        with self.scheduler.lock:
            status = {pod: (self.scheduler.resource_status.get(pod) or {})
                      .get("device-allocated") for pod in doc["assignments"]}
        for pod, node in doc["assignments"].items():
            request = self.books.requests[pod]
            grant = (status[pod] or {}).get("gpu")
            self.grants[pod] = grant
            self.events.append((
                "bind", pod, self.books.node_row.get(node, -1),
                int(request[dims["gpu"]]), int(request[dims["gpu_memory"]]),
                grant))
            self.gives_up_at.pop(pod, None)
            self.leaves_at[pod] = self.cycle_no + int(
                self.rng.integers(*lifetime, endpoint=True))
        return bound

    def spread_lifetimes(self) -> None:
        """After the fill: the remaining lifetimes of what is bound are
        spread over a whole lifetime, so that departures are steady from
        the first cycle."""
        top = self.mix["lifetime_cycles"][1]
        for pod in self.leaves_at:
            self.leaves_at[pod] = self.cycle_no + int(
                self.rng.integers(1, top, endpoint=True))

    # -- what the books say -------------------------------------------------

    def gpu_core_allocated_share(self) -> float:
        dims = self.dims
        total = int(self.books.alloc[:, dims["gpu"]].astype(np.int64).sum())
        used = sum(int(self.books.requests[p][dims["gpu"]])
                   for p in self.books.bound)
        return used / total

    def reported(self) -> dict:
        """Reported, not compared: the share of the cluster's GPU core that
        is granted, the nodes every device of which is usable and free, and
        the pods withdrawn, by request shape."""
        taken = {(self.books.bound[pod], int(g["minor"]))
                 for pod, grant in self.grants.items() if grant
                 for g in grant}
        whole_free_nodes = sum(
            all(d["healthy"] and (node, m) not in taken
                for m, d in enumerate(devices))
            for node, devices in self.inventory.items())
        return {"gpu_core_allocated_share": self.gpu_core_allocated_share(),
                "whole_free_nodes": whole_free_nodes,
                "withdrawn_by_request_shape": self.withdrawn_by_shape}

    # -- after the window ---------------------------------------------------

    def device_plane(self) -> dict:
        """The device-resident device plane, read back by node name."""
        sched = self.scheduler
        with sched.lock:
            sched.snapshot.flush()
            dev = sched.snapshot.state.devices
            index = dict(sched.snapshot.node_index)
            if dev is None:
                return {}
            fields = {k: np.asarray(getattr(dev, k)) for k in
                      ("free", "total", "valid", "healthy")}
        return {name: {k: v[row] for k, v in fields.items()}
                for name, row in index.items()}

    def _threshold_ok(self, node: int, request: np.ndarray) -> bool:
        """LoadAware's Filter at the program's defaults, as the
        configuration states them: round((usage + estimate) * 100 /
        allocatable) may not pass the threshold."""
        for dim, (factor, threshold) in self.config["loadaware"].items():
            d = self.dims[dim]
            total = int(self.books.alloc[node, d])
            estimate = (int(request[d]) * factor + 50) // 100
            used = int(self.books.usage[node, d]) + estimate
            if total > 0 and 100 * used + total // 2 >= (threshold + 1) * total:
                return False
        return True

    def verify(self) -> dict[str, int]:
        books, dims = self.books, self.dims
        compared = books.verify(self.held())
        n = len(books.node_names)
        slots = self.gpu["device_bucket"]
        replayed = reference.replay(n, slots, self.events)
        table = replayed["table"]
        for key in ("bind_without_grant", "grant_invalid",
                    "device_overcommit_cells"):
            compared[key] = replayed[key]

        # the device-resident free tensor against total less the grants
        # of the books' bound pods
        plane = self.device_plane()
        mismatch = abs(len(plane) - n)
        for name, row in books.node_row.items():
            held = plane.get(name)
            if held is None:
                continue
            width = min(slots, held["free"].shape[0])
            mismatch += int(np.count_nonzero(
                held["free"][:width].astype(np.int64) != table.free[row][:width]))
            mismatch += int(np.count_nonzero(
                held["total"][:width].astype(np.int64) != table.total[row][:width]))
            mismatch += int(np.count_nonzero(
                held["valid"][:width] != table.valid[row][:width]))
            mismatch += int(np.count_nonzero(
                held["healthy"][:width] != table.healthy[row][:width]))
        compared["device_state_mismatch"] = mismatch

        compared["standing_bound"] = len(self._standing_names & set(books.bound))

        # a pending device pod is diagnosed, and by the device dimension
        device_pending = [p for p in books.pending
                          if books.requests[p][dims["gpu"]] > 0]
        compared["undiagnosed"] = sum(
            "gpu" not in self.last_failures.get(p, "") for p in device_pending)

        # a pending pod the reference's Filter would still place on the
        # final state.  Bound: over the nodes that took no bind in the
        # last round (the program's rounds carry the estimated usage of
        # what they place, which the final state no longer shows).
        requested, _ = checks.requested_by_node(
            n, books.node_row, books.requests, books.bound, books.dims)
        free = books.alloc.astype(np.int64) - requested
        quiet = np.ones(n, bool)
        for node in self.last_bound.values():
            quiet[books.node_row[node]] = False
        missed = 0
        for pod in books.pending - self._standing_names:
            request = books.requests[pod].astype(np.int64)
            fits = np.all((request[None, :] <= free) | (request[None, :] == 0),
                          axis=1) & quiet
            core, memory = int(request[dims["gpu"]]), int(request[dims["gpu_memory"]])
            for node in np.flatnonzero(fits):
                if ((core <= 0 or table.node_fits(node, core, memory))
                        and self._threshold_ok(node, request)):
                    missed += 1
                    break
        compared["missed_device_fit"] = missed
        return compared
