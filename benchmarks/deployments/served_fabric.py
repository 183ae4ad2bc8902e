"""LLM training gangs on a labelled GPU fabric behind the served scheduler:
``served_socket``'s deployment (``koord-scheduler`` assembled by ``MAINS``,
rounds on its listen socket) with the layer label keys, a device inventory
on every node, and every event a ``STATE_PUSH`` frame on that socket.

What this shell adds to ``served_socket.Deployment``:

- the nodes arrive as ``node_upsert`` frames with their pod and rack labels
  and their 8 GPUs; which host sits in which rack slot is drawn from the
  seed;
- a gang arrives as a ``gang`` frame (min-member, must-gather layer, rack
  multiple) before its members' ``pod_add`` frames; it leaves whole when its
  lifetime in cycles ends;
- after every round it reads each new bind's ``device-allocated`` annotation
  from the scheduler's ``resource_status`` (what the embedding shell would
  write onto the pod at PreBind) into its books, and keeps, round by round,
  which gangs were pending and where each was bound;
- ``verify()`` replays those books through the plain references
  (``reference/topology.py``, ``reference/deviceshare.py``) and compares the
  guarantees the configuration states, all at limit 0.
"""

from __future__ import annotations

import json
import os

import numpy as np

from benchmarks.deployments import served_devices, served_socket
from benchmarks.reference import deviceshare
from benchmarks.reference import topology as reference

GATHERS = ("cluster", "pod", "rack")


class Deployment(served_socket.Deployment):

    def __init__(self, config: dict, sizes: dict, seed: int, run_dir: str):
        from koordinator_tpu.transport import deltasync

        if not hasattr(deltasync, "GANG"):
            # a program whose wire carries no gang record cannot be told a
            # gang's min-member or topology: it must end here, at once
            raise SystemExit(
                "benchmarks/deployments/served_fabric.py: this program's "
                "sync service has no gang kind (transport/deltasync.py has "
                "no GANG); the deployment cannot run on it")
        os.makedirs(run_dir, exist_ok=True)
        path = os.path.join(run_dir, "scheduler-config.json")
        with open(path, "w") as f:
            json.dump(config["scheduler_config"], f)
        config = dict(config, scheduler_flags=[
            *config["scheduler_flags"], "--config", path])
        super().__init__(config, sizes, seed, run_dir)
        self.first_round_s = None
        self.cycle_no = 0
        self.layers = config["topology"]["layers"]
        self.labels = config["topology"]["layer_labels"]
        self.member = self._member_request()
        #: host -> its pod and rack
        self.pod_of: dict[str, str] = {}
        self.rack_of: dict[str, str] = {}
        self.tree = reference.Tree(len(self.layers))
        #: gang -> {"members", "size", "gather", "rack_multiple", "lifetime"}
        self.gangs: dict[str, dict] = {}
        self.leaves_at: dict[str, int] = {}
        #: the books of the device plane, in order, for the replay
        self.dev_events: list[tuple] = []
        #: ("round", pending gangs, {pod: node}) and ("leave", [pods])
        self.log: list[tuple] = []
        self.arrived_now: list[str] = []
        self.unplaced = 0

    def _member_request(self) -> np.ndarray:
        dims, gpu, member = self.dims, self.config["gpu"], self.config["member"]
        request = np.zeros(dims["count"], np.int32)
        request[dims["cpu"]] = member["cpu_milli"]
        request[dims["memory"]] = member["memory_mib"]
        request[dims["gpu"]] = member["gpus"] * gpu["core"]
        request[dims["gpu_memory"]] = member["gpus"] * gpu["memory_mib"]
        return request

    # -- set-up -------------------------------------------------------------

    def load_nodes(self, replays: int = 1) -> None:
        if replays != 1:
            raise NotImplementedError("one assembled scheduler, one cluster")
        sizes, dims, gpu = self.sizes, self.dims, self.config["gpu"]
        n, per_rack = sizes["nodes"], sizes["nodes_per_rack"]
        per_pod = per_rack * sizes["racks_per_pod"]
        if n != per_pod * sizes["pods"]:
            raise ValueError(f"{n} nodes are not {sizes['pods']} pods of "
                             f"{per_pod}")
        alloc = np.zeros((n, dims["count"]), np.int32)
        alloc[:, dims["cpu"]] = self.config["node"]["cpu_milli"]
        alloc[:, dims["memory"]] = self.config["node"]["memory_mib"]
        alloc[:, dims["gpu"]] = gpu["per_node"] * gpu["core"]
        alloc[:, dims["gpu_memory"]] = gpu["per_node"] * gpu["memory_mib"]
        slot = self.rng.permutation(n)
        tokens = self.rng.integers(0, 16**6, n)
        names = [f"gpu-{i:04d}-{int(tokens[i]):06x}" for i in range(n)]
        self.books.set_nodes(names, alloc, np.zeros_like(alloc))
        pod_key, rack_key = self.labels
        for i, name in enumerate(names):
            s = int(slot[i])
            pod, rack = f"pod{s // per_pod}", f"rack{s // per_rack}"
            self.pod_of[name], self.rack_of[name] = pod, rack
            self.tree.add(i, [pod, rack, name])
            devices = [{"core": gpu["core"], "memory": gpu["memory_mib"],
                        "group": minor // gpu["group_size"], "healthy": True}
                       for minor in range(gpu["per_node"])]
            self.dev_events.append(("inventory", i,
                                    [dict(d) for d in devices]))
            self.push({"kind": "node_upsert", "name": name,
                       "labels": {pod_key: pod, rack_key: rack},
                       "devices": {"gpu": devices}},
                      {"allocatable": alloc[i],
                       "usage": np.zeros(dims["count"], np.int32)})
        self.tree.finish()

    # -- one cycle's steps --------------------------------------------------

    def depart(self) -> int:
        """The gangs whose lifetime ends leave, every member."""
        gone = sorted(g for g, at in self.leaves_at.items()
                      if at <= self.cycle_no)
        pods = []
        for gang in gone:
            del self.leaves_at[gang]
            for pod in self.gangs[gang]["members"]:
                self.books.leave(pod)
                self.dev_events.append(("leave", pod))
                self.push({"kind": "pod_remove", "name": pod})
                pods.append(pod)
        self.log.append(("leave", pods))
        return len(pods)

    def arrive(self, schedule: list[dict]) -> int:
        """The schedule's gangs, each record before its members, in the
        order their names sort (the scheduler's gang order)."""
        layer_of = {g: self.layers[g] for g in GATHERS}
        offered = 0
        self.arrived_now = []
        k = 0
        for kind in schedule:
            size = kind["nodes"]
            for _ in range(kind["per_cycle"]):
                uid = int(self.rng.integers(0, 16**6))
                gang = f"j{self.cycle_no:05d}.{k:02d}-{uid:06x}"
                k += 1
                members = [f"{gang}-{m:03d}" for m in range(size)]
                self.gangs[gang] = {
                    "members": members, "size": size,
                    "gather": layer_of[kind["gather"]],
                    "rack_multiple": kind["rack_multiple"],
                    "lifetime": kind["lifetime"]}
                self.push({"kind": "gang", "name": gang, "min_member": size,
                           "topology": {
                               "desired_slots": size,
                               "must_gather_layer": layer_of[kind["gather"]],
                               "layer_multiples": [
                                   1, 1, kind["rack_multiple"]]}})
                for pod in members:
                    self.books.offer(pod, self.member)
                    self.push({"kind": "pod_add", "name": pod, "gang": gang,
                               "priority": 9_000, "qos": self.qos["LS"]},
                              {"requests": self.member})
                self.arrived_now.append(gang)
                offered += size
        return offered

    def solve(self) -> int:
        """One round on the socket, then what it bound into the books: the
        grants the scheduler wrote for PreBind, the round for the replay."""
        import time

        from koordinator_tpu.transport.services import solve_remote

        pending = sorted(g for g, spec in self.gangs.items()
                         if any(p in self.books.pending
                                for p in spec["members"]))
        t0 = time.perf_counter()
        doc = solve_remote(self.connected())
        if self.first_round_s is None:
            self.first_round_s = time.perf_counter() - t0
        bound = self.books.record_round(doc)
        binds = doc["assignments"]
        dims = self.dims
        with self.scheduler.lock:
            status = {pod: (self.scheduler.resource_status.get(pod) or {})
                      .get("device-allocated") for pod in binds}
        for pod, node in binds.items():
            self.dev_events.append((
                "bind", pod, self.books.node_row.get(node, -1),
                int(self.member[dims["gpu"]]),
                int(self.member[dims["gpu_memory"]]),
                (status[pod] or {}).get("gpu")))
        self.log.append(("round", pending, dict(binds)))
        for gang in pending:
            spec = self.gangs[gang]
            if all(p in self.books.bound for p in spec["members"]):
                self.leaves_at.setdefault(
                    gang, self.cycle_no + spec["lifetime"])
        self.unplaced += sum(p in self.books.pending for g in self.arrived_now
                             for p in self.gangs[g]["members"])
        return bound

    # -- what the books say -------------------------------------------------

    def busy_racks(self) -> int:
        return len({self.rack_of[n] for n in self.books.bound.values()})

    def half_racks(self) -> int:
        """Racks with some node busy and some free (2 nodes a rack)."""
        per: dict[str, int] = {}
        for node in set(self.books.bound.values()):
            per[self.rack_of[node]] = per.get(self.rack_of[node], 0) + 1
        return sum(c != self.sizes["nodes_per_rack"] for c in per.values())

    def tree_shape(self) -> tuple | None:
        """(node rows, layers, topology nodes) of the scheduler's tree, or
        None in a program that builds none."""
        tree = getattr(self.scheduler, "topology_tree", None)
        if tree is None:
            return None
        rows, layers = tree.node_path.shape
        return int(rows), int(layers), int(tree.num_topo)

    # -- after the window ---------------------------------------------------

    def _gather_faults(self, gang: str, nodes: list[str]) -> int:
        spec = self.gangs[gang]
        faults = 0
        if spec["gather"] == self.layers["pod"]:
            faults += len({self.pod_of[n] for n in nodes}) != 1
        elif spec["gather"] == self.layers["rack"]:
            faults += len({self.rack_of[n] for n in nodes}) != 1
        per: dict[str, int] = {}
        for node in nodes:
            per[self.rack_of[node]] = per.get(self.rack_of[node], 0) + 1
        faults += any(c % spec["rack_multiple"] for c in per.values())
        return faults

    def replay_topology(self) -> dict[str, int]:
        """Round by round from an empty cluster: the reference's plans of
        the round's pending gangs on the state the books held before it,
        against where the program bound them."""
        names = self.books.node_names
        row = self.books.node_row
        free = self.books.alloc.astype(np.int64)
        member = self.member.astype(np.int64)
        live = np.zeros(len(names), np.int64)
        where: dict[str, str] = {}
        valid = np.ones(len(names), bool)
        counts = {"gang_split": 0, "gather_broken": 0, "node_double": 0,
                  "plan_mismatch": 0}
        for event in self.log:
            if event[0] == "leave":
                for pod in event[1]:
                    node = where.pop(pod, None)
                    if node is not None:
                        free[row[node]] += member
                        live[row[node]] -= 1
                continue
            _, pending, binds = event
            gangs = [{"members": np.stack(
                          [member] * len(self.gangs[g]["members"])),
                      "desired": self.gangs[g]["size"],
                      "gather": self.gangs[g]["gather"],
                      "mults": [1, 1, self.gangs[g]["rack_multiple"], 1]}
                     for g in pending]
            plans = reference.plan_round(self.tree, free.copy(), valid, gangs)
            for gang, plan in zip(pending, plans):
                members = self.gangs[gang]["members"]
                nodes = [binds[p] for p in members if p in binds]
                if 0 < len(nodes) < len(members):
                    counts["gang_split"] += 1
                if nodes:
                    counts["gather_broken"] += self._gather_faults(gang, nodes)
                want = sorted(names[r] for r, c in plan for _ in range(c))
                counts["plan_mismatch"] += int(sorted(nodes) != want)
            for pod, node in binds.items():
                if node not in row:
                    continue
                counts["node_double"] += int(live[row[node]] > 0)
                live[row[node]] += 1
                free[row[node]] -= member
                where[pod] = node
        return counts

    def verify(self) -> dict[str, int]:
        books = self.books
        compared = books.verify(self.held())
        n = len(books.node_names)
        slots = self.config["gpu"]["per_node"]
        replayed = deviceshare.replay(n, slots, self.dev_events)
        topo = self.replay_topology()
        compared["gang_split"] = topo["gang_split"]
        compared["gather_broken"] = topo["gather_broken"]
        compared["device_double"] = (replayed["device_overcommit_cells"]
                                     + topo["node_double"])
        compared["plan_mismatch"] = topo["plan_mismatch"]
        compared["unplaced_after_round"] = self.unplaced
        compared["bind_without_grant"] = replayed["bind_without_grant"]
        compared["grant_invalid"] = replayed["grant_invalid"]

        # the device-resident free tensor against total less the grants of
        # the books' bound pods
        table = replayed["table"]
        plane = served_devices.Deployment.device_plane(self)
        mismatch = abs(len(plane) - n)
        for name, r in books.node_row.items():
            held = plane.get(name)
            if held is None:
                continue
            width = min(slots, held["free"].shape[0])
            for key in ("free", "total"):
                mismatch += int(np.count_nonzero(
                    held[key][:width].astype(np.int64)
                    != getattr(table, key)[r][:width]))
            for key in ("valid", "healthy"):
                mismatch += int(np.count_nonzero(
                    held[key][:width] != getattr(table, key)[r][:width]))
        compared["device_state_mismatch"] = mismatch
        return compared
