"""One koord-scheduler and one koord-manager in one process:
``cmd.binaries.MAINS["koord-scheduler"]`` as ``served_socket`` assembles it,
and ``MAINS["koord-manager"]`` pointed at that scheduler's listen socket.
Nothing is wired here: the manager watches the scheduler's sync service over
the socket and pushes its noderesource patches back over it, its webhooks
admit the pods, and this shell only steps the report clock and calls
``colocation_loop.tick()`` (the loop's own docstring: the shell owns the
cadence).

What this file adds to ``served_socket.Deployment`` (which it builds on as
it finds it, so the control and the planted faults of ``benchmarks/tests``
reach this deployment too) is the data of a colocation cluster and the
books of its loop:

- it stands in for the cluster's koordlets: every report interval each
  node reports its usage, the system's share, and the sums over its prod
  and mid pods (usage, requests, max of the two), computed from this
  file's own pod table; a pod's usage is its request times a share drawn
  when it binds times its node's load factor, a sine over the cycles with
  a phase per node;
- it stands in for Spark: jobs of one driver and some executors arrive as
  plain cpu/memory pods with the colocation label, go through the
  manager's own mutating and validating webhooks, and what the webhook
  wrote is what ``add_pod`` is given; a job's pods leave when its lifetime
  is over;
- every tick is logged with what was reported going in and what stood
  after it (the manager's records say whom it patched, the sync service's
  store what arrived), every round with the batch allocatable that stood
  and the BE binds it answered, and ``verify`` replays them through
  ``reference.colocation``.
"""

from __future__ import annotations

import json
import os
import time

import numpy as np

from benchmarks.deployments import served_socket
from benchmarks.reference import checks, generators
from benchmarks.reference import colocation as reference

_BUILT: dict = {}

#: how long a wait for the manager's watch may take before the run gives up
CATCH_UP_TIMEOUT_S = 30.0


def __getattr__(name: str):
    if name != "Deployment":
        raise AttributeError(name)
    base = served_socket.Deployment
    if base not in _BUILT:
        _BUILT[base] = type("Deployment", (Colocated, base), {})
    return _BUILT[base]


def program_colocation_config(config) -> dict:
    """The manager's loaded ``ColocationConfig``, in the ConfigMap's words."""
    return {
        "enable": config.enable,
        "metricAggregateDurationSeconds":
            config.metric_aggregate_duration_seconds,
        "metricReportIntervalSeconds": config.metric_report_interval_seconds,
        "cpuReclaimThresholdPercent": config.cpu_reclaim_threshold_percent,
        "memoryReclaimThresholdPercent":
            config.memory_reclaim_threshold_percent,
        "cpuCalculatePolicy": config.cpu_calculate_policy,
        "memoryCalculatePolicy": config.memory_calculate_policy,
        "degradeTimeMinutes": config.degrade_time_minutes,
        "updateTimeThresholdSeconds": config.update_time_threshold_seconds,
        "resourceDiffThreshold": config.resource_diff_threshold,
        "midCPUThresholdPercent": config.mid_cpu_threshold_percent,
        "midMemoryThresholdPercent": config.mid_memory_threshold_percent,
        "midUnallocatedPercent": config.mid_unallocated_percent,
    }


def plain_pod(name: str, cpu_milli: int, memory_mib: int,
              labels: dict) -> dict:
    """A Spark pod as its operator creates it: plain cpu and memory."""
    resources = {"cpu": f"{cpu_milli}m", "memory": f"{memory_mib}Mi"}
    return {"metadata": {"name": name, "namespace": "spark",
                         "labels": dict(labels)},
            "spec": {"containers": [{"name": "spark", "resources": {
                "requests": dict(resources), "limits": dict(resources)}}]}}


def admitted(pod: dict) -> tuple:
    """What admission wrote on a pod, in a form that compares."""
    resources = pod["spec"]["containers"][0]["resources"]
    return (pod["metadata"]["labels"].get(reference.LABEL_QOS),
            pod["spec"].get("priority"), pod["spec"].get("schedulerName"),
            tuple(sorted(resources["requests"].items())),
            tuple(sorted(resources["limits"].items())))


class Colocated:
    """Mixed in over ``served_socket.Deployment`` (see ``__getattr__``)."""

    def __init__(self, config: dict, sizes: dict, seed: int, run_dir: str):
        super().__init__(config, sizes, seed, run_dir)
        from koordinator_tpu.api import crds
        from koordinator_tpu.cmd.binaries import MAINS

        self.colocation = config["sloconfig"]["colocation-config"]
        self.profile = config["colocation_profile"]
        self.now = float(config["clock"]["start"])
        path = os.path.join(run_dir, "slo-controller-config.yaml")
        with open(path, "w") as f:
            json.dump(config["sloconfig"], f)   # JSON is YAML
        try:
            self.manager = MAINS["koord-manager"](
                ["--disable-leader-election", "--scheduler-sidecar-addr",
                 self.sock, "--sloconfig-file", path,
                 *config.get("manager_flags", [])],
                clock=lambda: self.now)
            component = self.manager.component
            loaded = program_colocation_config(component.noderesource.config)
            if loaded != self.colocation:
                raise SystemExit(
                    f"configuration's colocation-config {self.colocation} "
                    f"is not what the manager loaded: {loaded}")
        except BaseException as err:
            # a program whose koord-manager takes no report clock must end
            # here, at once: the scheduler's listener would keep the
            # process alive
            super().close()
            raise SystemExit(f"cannot assemble koord-manager beside "
                             f"koord-scheduler: {err!r}") from err
        # the seam a watched ClusterColocationProfile CR would use
        component.pod_mutating.set_profiles([crds.ClusterColocationProfile(
            name=self.profile["name"],
            pod_selector=dict(self.profile["pod_selector"]),
            qos_class=self.profile["qos"],
            koordinator_priority=self.profile["priority"],
            scheduler_name=self.profile["scheduler_name"])])
        self.loop = component.colocation_loop
        d = self.dims
        self.own = np.array([d["cpu"], d["memory"]])
        self.batch = np.array([d["batch_cpu"], d["batch_memory"]])
        self.written = np.array([d["batch_cpu"], d["batch_memory"],
                                 d["mid_cpu"], d["mid_memory"]])
        #: the pod table, by serial number: the quantity a pod asks for on
        #: its two dimensions (its own, or the batch ones for a BE pod)
        cap = 2 * sizes["ls_pods"]
        self.p_row = np.full(cap, -1, np.int32)
        self.p_req = np.zeros((cap, 2), np.int64)
        self.p_base = np.zeros((cap, 2), np.float64)
        self.p_be = np.zeros(cap, bool)
        self.names: list[str] = []
        self.serial_of: dict[str, int] = {}
        #: offered and not yet seen bound: name -> serial
        self.awaiting: dict[str, int] = {}
        #: what admission wrote on every Spark pod, by serial
        self.admissions: dict[int, tuple] = {}
        self.job_pods: list[list[int]] = []
        self.ending: dict[int, list[int]] = {}
        self.cycle = 0
        self.phase = np.zeros(0)
        self.be_requested = np.zeros((sizes["nodes"], 2), np.int64)
        self.first_round_s = 0.0
        self.expected_paths: dict[str, str] = {}
        self._paths_checked = 0
        # the logs verify() replays
        self.tick_log: list[dict] = []
        self.round_log: list[dict] = []
        self.report: dict = {}
        #: the first tick dials: from then on the manager's watch is live
        self.paced = False
        self._unwatched = 0

    # -- the clock and the watch -----------------------------------------------

    def step_clock(self) -> None:
        self.cycle += 1
        self.now += self.config["clock"]["report_interval_seconds"]

    def catch_up(self, spans=None) -> None:
        """Wait until the manager's watch has applied the sync service's
        newest resource version."""
        self._unwatched = 0
        if not self.paced:
            return
        if spans is not None:
            with spans.span("watch_catchup"):
                return self.catch_up()
        rv, sync = self.sync.rv, self.manager.component.sync
        give_up = time.perf_counter() + CATCH_UP_TIMEOUT_S
        while sync.rv < rv:
            if time.perf_counter() > give_up:
                raise RuntimeError(
                    f"the manager's watch stands at rv {sync.rv}, the sync "
                    f"service at {rv}: it is not catching up")
            time.sleep(0.001)

    def sent(self, spans=None) -> None:
        """One more in-process event went out with the watch connected.
        Events go in stretches of ``watch_chunk``, the watch caught up
        after each: more than the watch connection's bounded send queue
        holds, outstanding at once, poisons it (``served_socket.offer``)."""
        self._unwatched += 1
        if self._unwatched >= self.config["clock"]["watch_chunk"]:
            self.catch_up(spans)

    # -- data -------------------------------------------------------------------

    def load_nodes(self, replays: int = 1) -> None:
        if replays != 1:
            raise NotImplementedError("one assembled pair, one cluster")
        n = self.sizes["nodes"]
        alloc, usage = generators.make_nodes(self.rng, n, self.dims)
        # the manager alone fills batch and mid allocatable; nothing has
        # reported yet
        alloc[:, self.written] = 0
        usage[:] = 0
        names = [f"n{i}" for i in range(n)]
        self.books.set_nodes(names, alloc, usage)
        self.capacity = alloc[:, self.own].astype(np.int64)
        self.phase = self.rng.random(n)
        #: the report interval of set-up in which a node's koordlet comes
        #: up; until then the manager knows the node by its upsert alone
        self.start_group = self.rng.integers(
            0, self.config["clock"]["bring_up_intervals"], n)
        self.reported_at = np.full(n, np.nan)
        self.hang_up()
        for i, name in enumerate(names):
            self.sync.upsert_node(name, alloc[i], usage=usage[i])

    def _new_serials(self, count: int) -> np.ndarray:
        first = len(self.names)
        while first + count > len(self.p_row):
            for name in ("p_row", "p_req", "p_base", "p_be"):
                old = getattr(self, name)
                new = np.full((2 * len(old),) + old.shape[1:],
                              -1 if name == "p_row" else 0, old.dtype)
                new[: len(old)] = old
                setattr(self, name, new)
        return np.arange(first, first + count)

    def ls_pods(self, count: int) -> list[tuple]:
        """``count`` online pods: northstar-10k's cpu and memory ranges on
        their own dimensions, half in the prod band and half in mid."""
        ranges, rng = self.config["value_ranges"], self.rng
        serials = self._new_serials(count)
        cpu = rng.integers(*ranges["pod_cpu_milli"], count)
        mem = rng.integers(*ranges["pod_memory_mib"], count)
        bands = ranges["priority_bands"]
        prio = np.where(rng.random(count) < 0.5,
                        rng.integers(*bands["prod"], count),
                        rng.integers(*bands["mid"], count))
        self.p_req[serials] = np.stack([cpu, mem], axis=1)
        out = []
        for i, serial in enumerate(serials):
            name = f"ls{serial}"
            request = np.zeros(self.dims["count"], np.int32)
            request[self.own] = self.p_req[serial]
            self._register(name, int(serial))
            out.append((name, request, int(prio[i]), self.qos["LS"], {}))
        return out

    def _register(self, name: str, serial: int) -> None:
        self.names.append(name)
        self.serial_of[name] = serial
        self.awaiting[name] = serial

    def spark_jobs(self, count: int, lifetimes: np.ndarray) -> list[tuple]:
        """``count`` Spark jobs that end ``lifetimes`` cycles from now: a
        driver and some executors each, every pod admitted by the
        manager's webhooks."""
        spark, rng = self.config["value_ranges"]["spark"], self.rng
        component = self.manager.component
        executors = rng.integers(spark["executors"][0],
                                 spark["executors"][1] + 1, count)
        out = []
        for j in range(count):
            pods = 1 + int(executors[j])
            serials = self._new_serials(pods)
            cpu = rng.integers(spark["pod_cpu_milli"][0],
                               spark["pod_cpu_milli"][1] + 1, pods)
            mem = rng.integers(spark["pod_memory_mib"][0],
                               spark["pod_memory_mib"][1] + 1, pods)
            job = len(self.job_pods)
            self.job_pods.append(serials.tolist())
            self.ending.setdefault(self.cycle + int(lifetimes[j]),
                                   []).append(job)
            self.p_req[serials] = np.stack([cpu, mem], axis=1)
            self.p_be[serials] = True
            for i, serial in enumerate(serials):
                name = f"spark/j{job}-{'driver' if i == 0 else f'exec{i}'}"
                pod = component.pod_mutating.mutate(plain_pod(
                    name, int(cpu[i]), int(mem[i]), self.profile["labels"]))
                refused = component.pod_validating.validate(pod)
                if refused:
                    raise RuntimeError(f"admission refused {name}: {refused}")
                wrote = admitted(pod)
                self.admissions[int(serial)] = wrote
                self._register(name, int(serial))
                out.append((name, reference.request_vector(pod, self.dims),
                            int(wrote[1] or 0), self.qos.get(wrote[0], 0),
                            {"labels": dict(pod["metadata"]["labels"])}))
        return out

    def job_lifetimes(self, count: int) -> np.ndarray:
        mean = self.report["job_lifetime_cycles"]
        return self.rng.integers(max(mean // 2, 1), mean + mean // 2 + 1,
                                 count)

    def note_binds(self) -> np.ndarray:
        """Pods the last answers bound get a row and a usage share;
        returns their serials in the order they were offered."""
        bound, row_of = self.books.bound, self.books.node_row
        done = [(name, serial) for name, serial in self.awaiting.items()
                if name in bound]
        serials = np.fromiter((s for _, s in done), np.int64, len(done))
        if not done:
            return serials
        share = self.rng.uniform(*self.config["value_ranges"]
                                 ["pod_usage_share"], (len(done), 2))
        self.p_base[serials] = self.p_req[serials] * share
        self.p_row[serials] = [row_of.get(bound[name], -1)
                               for name, _ in done]
        for name, _ in done:
            del self.awaiting[name]
        be = serials[self.p_be[serials]]
        np.add.at(self.be_requested, self.p_row[be], self.p_req[be])
        return serials

    # -- arrivals, on the watch's terms -------------------------------------------

    def offer(self, pods: list[tuple], counts: bool = True) -> None:
        self.hang_up()
        for name, request, priority, qos, extra in pods:
            self.books.offer(name, request, counts)
            self.sync.add_pod(name, request, priority=priority, qos=qos,
                              **extra)
            self.sent()

    def withdraw(self, pods: list[tuple]) -> None:
        self.hang_up()
        for name, *_ in pods:
            self.books.withdraw(name)
            self.sync.remove_pod(name)
            self.sent()

    # -- set-up -------------------------------------------------------------------

    def solve_until_bound(self, pods: list[tuple], extra_rounds: int = 2
                          ) -> float:
        """One round, and up to ``extra_rounds`` more while any of ``pods``
        still waits; returns the first round's wall."""
        t0 = time.perf_counter()
        self.solve()
        first = time.perf_counter() - t0
        for _ in range(extra_rounds):
            if not any(p[0] in self.books.pending for p in pods):
                break
            self.solve()
        return first

    def fill(self, params: dict) -> None:
        """The online pods, ``fill_waves`` waves through the drain's
        arrival path with no watcher connected, one round each: the
        warm-up of the full programs too (``warm_standing`` as in
        ``drain3``)."""
        waves = params["fill_waves"]
        standing = params.get("warm_standing", [])
        for k in range(waves):
            self.set_standing(standing[k] if k < len(standing)
                              else self.sizes["standing"])
            pods = self.ls_pods(self.sizes["ls_pods"] // waves)
            self.offer(pods, counts=False)
            first = self.solve_until_bound(pods)
            if k == 0:
                self.first_round_s = first
        self.set_standing(self.sizes["standing"])

    def fill_batch(self, params: dict) -> None:
        """Spark jobs up to ``be_fill_share`` of the cluster's batch CPU as
        it stands now, their remaining lifetimes spread so that departures
        are steady from the first cycle on."""
        share = self.config["value_ranges"]["be_fill_share"]
        spark = self.config["value_ranges"]["spark"]
        want = share * int(self.books.alloc[:, self.dims["batch_cpu"]]
                           .astype(np.int64).sum())
        pods_per_job = 1 + (spark["executors"][0] + spark["executors"][1]) / 2
        cpu_per_job = pods_per_job * sum(spark["pod_cpu_milli"]) / 2
        jobs = max(int(want / cpu_per_job), 1)
        self.report["fill_jobs"] = jobs
        self.report["job_lifetime_cycles"] = max(
            round(jobs / params["jobs_per_cycle"]), 2)
        # the jobs a running cluster holds: a lifetime met in proportion
        # to its length, at an age uniform over it
        mean = self.report["job_lifetime_cycles"]
        lengths = np.arange(max(mean // 2, 1), mean + mean // 2 + 1)
        life = self.rng.choice(lengths, jobs, p=lengths / lengths.sum())
        remaining = life - (self.rng.random(jobs) * life).astype(np.int64)
        pods = self.spark_jobs(jobs, remaining)
        self.report["fill_pods"] = len(pods)
        self.offer(pods, counts=False)
        self.solve_until_bound(pods)

    # -- one cycle's steps --------------------------------------------------------

    def load_factor(self) -> np.ndarray:
        load = self.config["value_ranges"]["node_load"]
        return 1.0 + load["amplitude"] * np.sin(
            2.0 * np.pi * (self.cycle / load["period_cycles"] + self.phase))

    def reports(self) -> dict:
        """What every node's koordlet reports now: (N, 2) each."""
        n, nodes = len(self.names), self.sizes["nodes"]
        rows = self.p_row[:n]
        on = rows >= 0
        rows, be = rows[on], self.p_be[:n][on]
        request = self.p_req[:n][on]
        used = (self.p_base[:n][on]
                * self.load_factor()[rows][:, None]).astype(np.int64)

        def by_node(values: np.ndarray, pick: np.ndarray) -> np.ndarray:
            return np.stack([np.bincount(
                rows[pick], weights=values[pick, dim], minlength=nodes)
                for dim in (0, 1)], axis=1).astype(np.int64)

        system = self.capacity // self.config["value_ranges"][
            "system_usage_divisor"]
        hp_usage = by_node(used, ~be)
        return {"sys_usage": system, "hp_usage": hp_usage,
                "hp_request": by_node(request, ~be),
                "hp_max_used_req": by_node(np.maximum(request, used), ~be),
                "usage": system + hp_usage, "be_usage": by_node(used, be)}

    def usage_wave(self, spans=None, groups: int | None = None) -> None:
        """Every node (in set-up: every node of the first ``groups`` start
        groups) reports all five vectors and the report time."""
        report = self.reports()
        nodes, dims = self.sizes["nodes"], self.dims["count"]
        up = (np.ones(nodes, bool) if groups is None
              else self.start_group < groups)
        self.reported_at[up] = self.now

        def full(values: np.ndarray) -> np.ndarray:
            out = np.zeros((nodes, dims), np.int32)
            out[:, self.own] = values
            return out

        vectors = {k: full(report[k]) for k in (
            "usage", "sys_usage", "hp_usage", "hp_request",
            "hp_max_used_req")}
        vectors["usage"][:, self.batch] = report["be_usage"]
        self.hang_up()
        books, update, now = self.books, self.sync.update_node_usage, self.now
        books.usage[up] = vectors["usage"][up]
        # a node that has not reported yet is known to the manager by its
        # upsert: no usage at all, dated when the watch was bootstrapped
        self.pending_report = dict(
            {k: np.where(up[:, None], report[k], 0)
             for k in ("usage", "sys_usage", "hp_usage", "hp_request",
                       "hp_max_used_req")}, now=now)
        names = books.node_names
        usage, sys_usage = vectors["usage"], vectors["sys_usage"]
        hp_usage, hp_request = vectors["hp_usage"], vectors["hp_request"]
        hp_max = vectors["hp_max_used_req"]
        for row in np.flatnonzero(up):
            update(names[row], usage[row], sys_usage=sys_usage[row],
                   hp_usage=hp_usage[row], hp_request=hp_request[row],
                   hp_max_used_req=hp_max[row], report_time=now)
            self.sent(spans)
        # the tick must see the whole wave
        self.catch_up(spans)

    def tick(self) -> int:
        """One ``colocation_loop.tick()``; logs what was reported going
        in, whom the manager's records say it patched, and what the sync
        service holds of every node after it."""
        # the patches are broadcast to every connection: this shell's own
        # client must not stand in their way
        self.hang_up()
        pushed = self.loop.tick()
        if not self.paced:
            # the first tick dialled and bootstrapped the watch: the
            # snapshot's upserts were dated by the clock as it stands
            self.paced, self.bootstrapped_at = True, self.now
        if self.loop.connect_failures or self.loop.push_failures:
            raise RuntimeError(
                f"the colocation loop lost its sidecar: connect failures "
                f"{self.loop.connect_failures}, push failures "
                f"{self.loop.push_failures}")
        records = self.manager.component.sync_binding.records
        names, nodes, now = self.books.node_names, self.sync.nodes, self.now
        patched = np.fromiter(
            (name in records and records[name].last_sync_time == now
             for name in names), bool, len(names))
        stored = np.stack([nodes[name]["arrays"]["allocatable"]
                           for name in names])
        self.books.alloc[:] = stored
        self.tick_log.append(dict(self.pending_report, patched=patched,
                                  report_time=np.where(
                                      np.isnan(self.reported_at),
                                      self.bootstrapped_at,
                                      self.reported_at),
                                  stored=stored[:, self.written]
                                  .astype(np.int64), pushed=pushed))
        return pushed

    def depart(self) -> int:
        """The pods of the jobs that end this cycle leave."""
        serials = [s for job in self.ending.pop(self.cycle, [])
                   for s in self.job_pods[job]]
        self.hang_up()
        books = self.books
        bound = np.asarray([s for s in serials if self.p_row[s] >= 0],
                           np.int64)
        for s in serials:
            name = self.names[s]
            if name in books.bound:
                books.leave(name)
            elif name in books.pending:
                books.withdraw(name)
                self.awaiting.pop(name, None)
            else:
                continue
            self.sync.remove_pod(name)
            self.sent()
        np.subtract.at(self.be_requested, self.p_row[bound],
                       self.p_req[bound])
        self.p_row[bound] = -1
        return len(serials)

    def solve(self) -> int:
        """One round on the socket; its BE binds are logged against the
        batch allocatable that stood."""
        requested = self.be_requested.copy()
        bound = super().solve()
        serials = self.note_binds()
        be = serials[self.p_be[serials]]
        self.round_log.append({
            "tick": len(self.tick_log) - 1, "requested": requested,
            "rows": self.p_row[be].copy(), "requests": self.p_req[be].copy()})
        return bound

    def squeezed_nodes(self) -> int:
        batch = self.books.alloc[:, self.batch].astype(np.int64)
        return int(np.any(self.be_requested > batch, axis=1).sum())

    # -- rounds -------------------------------------------------------------------

    def path_ok(self, path: str, want) -> bool:
        """``want`` names the positions of a cycle's rounds in order; the
        traffic mix says which path each takes (``expected_paths``)."""
        position = want[self._paths_checked % len(want)]
        self._paths_checked += 1
        return super().path_ok(path, self.expected_paths.get(position, path))

    # -- after the window ---------------------------------------------------------

    def verify(self) -> dict[str, int]:
        held = self.held()
        compared = self.books.verify(held)
        books = self.books
        n = len(books.node_names)
        # a node whose batch allocatable shrank under its bound BE pods
        # stands over it, by design: upstream leaves such a node to the
        # koordlet.  Overcommit is held on every other dimension.
        requested, _ = checks.requested_by_node(
            n, books.node_row, books.requests, books.bound, books.dims)
        others = np.setdiff1d(np.arange(books.dims), self.batch)
        compared["overcommit_cells"] = checks.overcommit_cells(
            books.alloc[:, others], requested[:, others])
        compared.update(self.verify_colocation(held))
        return compared

    def verify_colocation(self, held: dict) -> dict[str, int]:
        ticks = reference.replay_ticks(self.capacity, self.tick_log,
                                       self.colocation)
        set_mismatch = value_mismatch = 0
        for logged, want in zip(self.tick_log, ticks):
            set_mismatch += int(np.count_nonzero(
                logged["patched"] != want["patched"]))
            value_mismatch += checks.mismatch_cells(
                logged["stored"], np.maximum(want["standing"], 0))
        names = self.books.node_names
        standing = (np.maximum(ticks[-1]["standing"], 0) if ticks
                    else np.zeros((len(names), 4), np.int64))
        blank = np.full(self.dims["count"], -1)
        rows = np.stack([held["alloc"].get(name, blank) for name in names])
        state_mismatch = (
            checks.mismatch_cells(rows[:, self.written], standing)
            + checks.mismatch_cells(rows[:, self.own], self.capacity))
        over, on_squeezed, _ = reference.bind_violations([
            dict(entry, batch=np.maximum(
                ticks[entry["tick"]]["standing"][:, :2], 0))
            for entry in self.round_log if entry["tick"] >= 0])
        admission = 0
        for serial, wrote in self.admissions.items():
            cpu, mem = self.p_req[serial]
            want = reference.admit(plain_pod(
                self.names[serial], int(cpu), int(mem),
                self.profile["labels"]), self.profile)
            admission += admitted(want) != wrote
        off_batch = 0
        for pod, serial in self.serial_of.items():
            if self.p_be[serial] and pod in self.books.bound:
                charged = np.asarray(held["bound_requests"].get(
                    pod, np.zeros(self.dims["count"])))
                off_batch += bool(np.delete(charged, self.batch).any())
        return {"patch_set_mismatch": set_mismatch,
                "patch_value_mismatch": value_mismatch,
                "allocatable_state_mismatch": state_mismatch,
                "batch_overcommit_at_bind": over,
                "bind_on_squeezed_node": on_squeezed,
                "admission_mismatch": admission,
                "be_pod_charged_off_batch_dims": off_batch}

    def close(self) -> None:
        self.manager.stop()
        super().close()
