"""One koord-scheduler and one koord-descheduler in one process, over one
device-resident cluster state: ``cmd.binaries.MAINS["koord-scheduler"]``
as ``served_socket`` assembles it, and ``MAINS["koord-descheduler"]`` handed
that assembly.  Nothing is wired here: LowNodeLoad, the migration
controller, reservation-first and the controller finder are the program's
own wiring (``main_koord_descheduler(..., scheduler=)``).

What this file adds to ``served_socket.Deployment`` (which it builds on as
it finds it, so the control and the planted faults of ``benchmarks/tests``
reach this deployment too) is the data of a running cluster and the books
of a rebalance:

- pods come in workloads of ``replicas`` pods from one template (requests,
  priority, QoS drawn by ``reference.generators.make_pods``), named
  ``ns<k>/p<serial>``, labelled ``app=w<n>`` and owned by ``Deployment/w<n>``
  (``daemonset_workloads`` of them by ``DaemonSet/w<n>``: the evictor
  filter never lets such a pod go);
- every bound pod has a usage: its request times a share drawn once when it
  binds, dimension by dimension (a BE pod's usage therefore reads on its
  batch dimensions); a node's usage is the sum of its pods' plus a
  twentieth of its allocatable; heating a node scales its pods' CPU usage
  up to a target;
- each descheduling round and each reconcile is logged with what THESE
  books held going in (the pod table, the usage, the jobs that are live
  and what each is: node, namespace, workload, priority, order of
  creation) and what the program did (whom it chose, whom it let run, whom
  it evicted), and ``verify`` replays them through
  ``reference.lownodeload``.
"""

from __future__ import annotations

import sys

import numpy as np

from benchmarks.deployments import served_socket
from benchmarks.reference import generators
from benchmarks.reference import lownodeload as reference

_BUILT: dict = {}


def __getattr__(name: str):
    if name != "Deployment":
        raise AttributeError(name)
    base = served_socket.Deployment
    if base not in _BUILT:
        _BUILT[base] = type("Deployment", (Colocated, base), {})
    return _BUILT[base]


def program_defaults(component) -> dict:
    """The program's loaded descheduler options, in the configuration
    file's words."""
    from koordinator_tpu.api.resources import ResourceDim

    lnl, lim = component.lownodeload, component.migration_limits
    cpu, mem = int(ResourceDim.CPU), int(ResourceDim.MEMORY)
    low, high = np.asarray(lnl.low_thresholds), np.asarray(lnl.high_thresholds)
    return {
        "low_thresholds": {"cpu": int(low[cpu]), "memory": int(low[mem])},
        "high_thresholds": {"cpu": int(high[cpu]), "memory": int(high[mem])},
        "configured_dims": int((low >= 0).sum()),
        "use_deviation_thresholds": bool(lnl.use_deviation),
        "anomaly_rounds": int(lnl.anomaly_rounds),
        "max_migrating_per_node": lim.max_migrating_per_node,
        "max_migrating_per_namespace": lim.max_migrating_per_namespace,
        "max_migrating_per_workload": lim.max_migrating_per_workload,
        "max_unavailable_per_workload": lim.max_unavailable_per_workload,
    }


class Colocated:
    """Mixed in over ``served_socket.Deployment`` (see ``__getattr__``)."""

    def __init__(self, config: dict, sizes: dict, seed: int, run_dir: str):
        super().__init__(config, sizes, seed, run_dir)
        from koordinator_tpu.cmd.binaries import MAINS

        try:
            self.descheduler = MAINS["koord-descheduler"](
                ["--disable-leader-election", "--deschedule-plugins",
                 "LowNodeLoad", *config.get("descheduler_flags", [])],
                scheduler=self.assembled)
            self.defaults = program_defaults(
                self.descheduler.component_config)
            if self.defaults != config["program_defaults"]:
                raise SystemExit(
                    f"configuration's program_defaults "
                    f"{config['program_defaults']} are not the program's "
                    f"{self.defaults}")
        except BaseException as err:
            # a program that cannot assemble the pair (one from before
            # koord-descheduler took a scheduler) must end here, at once:
            # the scheduler's listener would keep the process alive
            super().close()
            raise SystemExit(f"cannot assemble koord-descheduler beside "
                             f"koord-scheduler: {err!r}") from err
        self.migration = self.descheduler.migration
        d = self.dims
        self.cpu, self.mem = d["cpu"], d["memory"]
        self.t_req, self.t_prio, self.t_qos = generators.make_pods(
            self.rng, sizes["workloads"], d, self.qos)
        #: every ``workloads / daemonset_workloads``-th workload is a
        #: DaemonSet
        every = sizes["workloads"] // sizes["daemonset_workloads"]
        self.t_daemonset = np.arange(sizes["workloads"]) % every == every - 1
        #: the pod table, by serial number
        cap = 2 * sizes["fill_pods"] + 1024
        self.p_row = np.full(cap, -1, np.int32)
        self.p_usage = np.zeros((cap, d["count"]), np.int32)
        self.p_workload = np.zeros(cap, np.int32)
        self.names: list[str] = []
        #: offered and not yet seen bound: name -> serial
        self.awaiting: dict[str, int] = {}
        #: serials whose usage the scheduler's column does not hold yet
        self.usage_stale: list[int] = []
        self.heated = np.zeros(sizes["nodes"], bool)
        self.first_round_s = 0.0
        self.expected_paths: dict[str, str] = {}
        self._paths_checked = 0
        # the logs verify() replays, and what it counts as it goes
        self.desched_log: list[dict] = []
        self.reconcile_log: list[dict] = []
        #: the jobs that are live, by their pod, in order of creation:
        #: what the books know of each, and the program's job object (read
        #: for its outcome only)
        self.live: dict[str, dict] = {}
        self.job_of: dict[str, object] = {}
        self.jobs_made = 0
        self.reservation_of: dict[str, tuple] = {}
        self.replacements: list[str] = []
        self.evicted: list[str] = []
        self.evicted_without_reservation = 0
        self.reserve_rounds = 0

    # -- data -----------------------------------------------------------------

    def _grow(self) -> None:
        for name in ("p_row", "p_usage", "p_workload"):
            old = getattr(self, name)
            new = np.full((2 * len(old),) + old.shape[1:],
                          -1 if name == "p_row" else 0, old.dtype)
            new[: len(old)] = old
            setattr(self, name, new)

    def pods_of(self, workloads: np.ndarray) -> list[tuple]:
        """One new pod per entry of ``workloads`` (workload numbers)."""
        namespaces = self.sizes["namespaces"]
        out = []
        for w in workloads:
            w = int(w)
            serial = len(self.names)
            if serial == len(self.p_row):
                self._grow()
            name = f"ns{w % namespaces}/p{serial}"
            self.names.append(name)
            self.p_workload[serial] = w
            self.awaiting[name] = serial
            out.append((name, self.t_req[w], int(self.t_prio[w]),
                        int(self.t_qos[w]),
                        {"labels": {"app": f"w{w}"},
                         "owner": self.owner_of(w)}))
        return out

    def owner_of(self, workload: int) -> str:
        kind = "DaemonSet" if self.t_daemonset[workload] else "Deployment"
        return f"{kind}/w{workload}"

    def note_binds(self) -> None:
        """Pods the last answers bound get a row and a usage."""
        bound, row_of = self.books.bound, self.books.node_row
        done = [(name, serial) for name, serial in self.awaiting.items()
                if name in bound]
        if not done:
            return
        serials = np.fromiter((s for _, s in done), np.int64, len(done))
        share = self.rng.uniform(*self.config["value_ranges"]
                                 ["pod_usage_share"], len(done))
        self.p_usage[serials] = (
            self.t_req[self.p_workload[serials]] * share[:, None])
        self.p_row[serials] = [row_of.get(bound[name], -1)
                               for name, _ in done]
        self.usage_stale.extend(int(s) for s in serials)
        for name, _ in done:
            del self.awaiting[name]

    def node_usage(self) -> np.ndarray:
        """(N, R) int32: the sum of each node's pods' usage plus the
        system's share of its allocatable."""
        n = len(self.names)
        rows = self.p_row[:n]
        on = rows >= 0
        usage = (self.books.alloc.astype(np.int64)
                 // self.config["value_ranges"]["system_usage_divisor"])
        for dim in np.flatnonzero(self.t_req.any(axis=0)):
            usage[:, dim] += np.bincount(
                rows[on], weights=self.p_usage[:n, dim][on],
                minlength=len(usage)).astype(np.int64)
        return usage.astype(np.int32)

    def threshold_vectors(self) -> tuple[np.ndarray, np.ndarray]:
        """The low and the high thresholds as (R,) vectors, -1 where a
        dimension is not configured."""
        out = []
        for by_name in (self.defaults["low_thresholds"],
                        self.defaults["high_thresholds"]):
            vector = np.full(self.dims["count"], -1, np.int64)
            vector[self.cpu], vector[self.mem] = (by_name["cpu"],
                                                  by_name["memory"])
            out.append(vector)
        return out[0], out[1]

    def heat(self, count: int, usage: np.ndarray) -> np.ndarray:
        """``count`` nodes under the low thresholds, with pods, not heated
        before: their pods' CPU usage is scaled so the node reads a target
        drawn from ``heated_node_cpu_share``.  Returns ``usage`` with
        those rows redone."""
        if not count:
            return usage
        n = len(self.names)
        alloc = self.books.alloc.astype(np.int64)
        pct = reference.usage_percent(usage, alloc)
        valid = np.ones(len(alloc), bool)
        low, high = reference.thresholds(
            *self.threshold_vectors(),
            self.defaults["use_deviation_thresholds"], pct, valid)
        under, _ = reference.classify(pct, low, high, valid)
        pods_on = np.bincount(self.p_row[:n][self.p_row[:n] >= 0],
                              minlength=len(alloc))
        cool = np.flatnonzero(under & (pods_on > 0) & ~self.heated)
        rows = self.rng.choice(cool, min(count, len(cool)), replace=False)
        targets = self.rng.uniform(
            *self.config["value_ranges"]["heated_node_cpu_share"], len(rows))
        divisor = self.config["value_ranges"]["system_usage_divisor"]
        order = np.argsort(self.p_row[:n], kind="stable")
        starts = np.searchsorted(self.p_row[:n][order], rows)
        ends = np.searchsorted(self.p_row[:n][order], rows, side="right")
        usage = usage.copy()
        for row, target, lo, hi in zip(rows, targets, starts, ends):
            serials = order[lo:hi]
            system = int(alloc[row, self.cpu]) // divisor
            now = int(self.p_usage[serials, self.cpu].sum())
            want = target * int(alloc[row, self.cpu]) - system
            scaled = (self.p_usage[serials, self.cpu].astype(np.float64)
                      * (want / max(now, 1))).astype(np.int32)
            self.p_usage[serials, self.cpu] = scaled
            usage[row, self.cpu] = system + int(scaled.sum())
            self.usage_stale.extend(int(s) for s in serials)
            self.heated[row] = True
        return usage

    # -- set-up ---------------------------------------------------------------

    def fill(self, params: dict) -> None:
        """The cluster's running pods: ``fill_waves`` waves through the
        drain's arrival path, one round each.  They are the warm-up of the
        full programs too; ``warm_standing`` as in ``drain3``."""
        import time

        waves = params["fill_waves"]
        per_wave = self.sizes["workloads"] // waves
        standing = params.get("warm_standing", [])
        for k in range(waves):
            self.set_standing(standing[k] if k < len(standing)
                              else self.sizes["standing"])
            workloads = np.repeat(
                np.arange(k * per_wave, (k + 1) * per_wave),
                self.sizes["fill_pods"] // self.sizes["workloads"])
            pods = self.pods_of(self.rng.permutation(workloads))
            self.offer(pods, counts=False)
            t0 = time.perf_counter()
            self.solve()
            if k == 0:
                self.first_round_s = time.perf_counter() - t0
            for _ in range(2):
                if not any(p[0] in self.books.pending for p in pods):
                    break
                self.solve()
            self.note_binds()
        self.set_standing(self.sizes["standing"])

    # -- one cycle's steps ----------------------------------------------------

    def usage_wave(self, heat: int) -> None:
        """Every node reports; ``heat`` more nodes run hot from now on."""
        usage = self.heat(heat, self.node_usage())
        if self.usage_stale:
            stale = np.unique(np.asarray(self.usage_stale, np.int64))
            self.usage_stale.clear()
            self.scheduler.set_pod_usage(
                [self.names[s] for s in stale], self.p_usage[stale])
        self.hang_up()
        books, update = self.books, self.sync.update_node_usage
        books.usage[:] = usage
        for row, name in enumerate(books.node_names):
            update(name, usage[row])

    def report_again(self, count: int) -> None:
        """``count`` nodes drawn by the seed report the usage the books
        hold of them once more."""
        rows = self.rng.choice(len(self.books.node_names), count,
                               replace=False)
        for row in rows:
            self.sync.update_node_usage(self.books.node_names[row],
                                        self.books.usage[row])

    def deschedule(self) -> int:
        """One ``Descheduler.run_once()``; logs what the books held and
        whom the program chose."""
        n = len(self.names)
        jobs = self.migration.jobs
        known = len(jobs)
        entry = {"usage": self.books.usage.copy(),
                 "row": self.p_row[:n].copy(),
                 "pod_usage": self.p_usage[:n].copy(),
                 "migrating": list(self.live)}
        self.descheduler.component.run_once()
        made = list(jobs.values())[known:]
        entry["victims"] = [job.pod for job in made]
        self.desched_log.append(entry)
        node_names = self.books.node_names
        for job in made:
            serial = int(job.pod.rsplit("/p", 1)[1])
            workload = int(self.p_workload[serial])
            self.jobs_made += 1
            self.job_of[job.pod] = job
            self.live[job.pod] = {
                "name": job.pod, "node": node_names[self.p_row[serial]],
                "namespace": job.pod.split("/", 1)[0],
                "workload": self.owner_of(workload),
                "priority": int(self.t_prio[workload]),
                "created": self.jobs_made, "running": False}
        return len(made)

    def reconcile(self, spans) -> list:
        """One ``MigrationController.reconcile()``, its reservation round
        under the benchmark's ``solve_request`` span; the answers of that
        round go into the books like any other's.  Returns the jobs that
        succeeded: their pods are gone."""
        from koordinator_tpu.descheduler.migration import MigrationJobPhase
        from koordinator_tpu.scheduler.scheduler import RSV_POD_PREFIX

        sched, migration = self.scheduler, self.migration
        n = len(self.names)
        waiting = [pod for pod, doc in self.live.items()
                   if not doc["running"]]
        self.reconcile_log.append({
            "pending": [dict(self.live[pod]) for pod in waiting],
            "running": [dict(doc) for doc in self.live.values()
                        if doc["running"]],
            "replicas": np.bincount(
                self.p_workload[:n][self.p_row[:n] >= 0],
                minlength=len(self.t_req))})
        inner, results = sched.schedule_round, []

        def spanned_round():
            with spans.span("solve_request"):
                results.append(inner())
            return results[-1]

        sched.schedule_round = spanned_round
        try:
            migration.reconcile()
        finally:
            del sched.schedule_round
        self.reserve_rounds += len(results)
        for result in results:
            self.books.record_round({
                "assignments": {p: node for p, node
                                in result.assignments.items()
                                if not p.startswith(RSV_POD_PREFIX)},
                "failures": {p: "" for p in result.failures
                             if not p.startswith(RSV_POD_PREFIX)}})
        self.note_binds()
        self.reconcile_log[-1]["allowed"] = [
            pod for pod in waiting
            if self.job_of[pod].phase is not MigrationJobPhase.PENDING]
        succeeded = []
        for pod in list(self.live):
            job = self.job_of[pod]
            if job.phase is MigrationJobPhase.RUNNING:
                self.live[pod]["running"] = True
                continue
            if job.phase is MigrationJobPhase.PENDING:
                continue
            del self.live[pod], self.job_of[pod]
            if job.phase is MigrationJobPhase.SUCCEEDED:
                succeeded.append(job)
        for job in succeeded:
            spec = sched.reservations.get(job.reservation or "")
            if (spec is None or spec.phase.value != "Available"
                    or spec.node in (None, job.node)):
                self.evicted_without_reservation += 1
            if spec is not None and spec.node is not None:
                self.reservation_of[job.reservation] = (spec.node, job.node)
            self.evicted.append(job.pod)
            if job.pod in self.books.bound:
                self.books.leave(job.pod)
            self.p_row[int(job.pod.rsplit("/p", 1)[1])] = -1
        return succeeded

    def replace(self, succeeded: list) -> int:
        """The pod each victim's controller creates in its place."""
        pods = self.pods_of(np.fromiter(
            (self.p_workload[int(job.pod.rsplit("/p", 1)[1])]
             for job in succeeded), np.int64, len(succeeded)))
        self.replacements.extend(p[0] for p in pods)
        self.offer(pods)
        return len(pods)

    # -- rounds ---------------------------------------------------------------

    def path_ok(self, path: str, want) -> bool:
        """``want`` names the positions of a cycle's rounds in order; the
        traffic mix says which path each takes (``expected_paths``)."""
        position = want[self._paths_checked % len(want)]
        self._paths_checked += 1
        return super().path_ok(path, self.expected_paths.get(position, path))

    # -- after the window -----------------------------------------------------

    def verify(self) -> dict[str, int]:
        compared = super().verify()
        compared.update(self.verify_rebalance())
        return compared

    def verify_rebalance(self) -> dict[str, int]:
        defaults = self.defaults
        plugin = reference.LowNodeLoad(
            *self.threshold_vectors(),
            defaults["use_deviation_thresholds"], defaults["anomaly_rounds"])
        alloc = self.books.alloc
        valid = np.ones(len(alloc), bool)
        serial_of = {name: i for i, name in enumerate(self.names)}
        victim_mismatch = victim_not_hot = 0
        plugin_rounds: list[int] = []
        for entry in self.desched_log:
            rows = entry["row"]
            n = len(rows)
            priority = self.t_prio[self.p_workload[:n]]
            evictable = (rows >= 0) & ~self.t_daemonset[self.p_workload[:n]]
            evictable[np.asarray(
                [serial_of[p] for p in entry["migrating"]
                 if p in serial_of and serial_of[p] < n], np.int64)] = False
            # the reference breaks ties between equally cheap pods by
            # position: it gets the pods in the order of their names
            by_name = np.argsort(np.asarray(self.names[:n]))
            want, abnormal = plugin.round(
                entry["usage"], alloc, valid, rows[by_name],
                entry["pod_usage"][by_name], priority[by_name],
                evictable[by_name])
            want = by_name[want].tolist()
            took = [serial_of.get(p, -1) for p in entry["victims"]]
            victim_mismatch += len(set(want) ^ set(took))
            for s in sorted(set(want) ^ set(took)):
                print(f"VICTIM_MISMATCH round {len(plugin_rounds)} pod "
                      f"{self.names[s]} reference_chose {s in set(want)} "
                      f"node_row {rows[s]} priority {priority[s]} usage "
                      f"{entry['pod_usage'][s].tolist()}", file=sys.stderr)
            plugin_rounds.append(len(took))
            victim_not_hot += sum(
                1 for s in took
                if s < 0 or not evictable[s] or not abnormal[rows[s]])

        limits = {"per_node": defaults["max_migrating_per_node"],
                  "per_namespace": defaults["max_migrating_per_namespace"],
                  "migrating_per_workload":
                      defaults["max_migrating_per_workload"],
                  "unavailable_per_workload":
                      defaults["max_unavailable_per_workload"]}
        arbitration_mismatch = limit_exceeded = 0
        for entry in self.reconcile_log:
            replicas = {self.owner_of(w): int(c)
                        for w, c in enumerate(entry["replicas"]) if c}
            want = reference.arbitrate(entry["pending"], entry["running"],
                                       limits, replicas)
            arbitration_mismatch += len(set(want) ^ set(entry["allowed"]))
            allowed = set(entry["allowed"])
            running = entry["running"] + [j for j in entry["pending"]
                                          if j["name"] in allowed]
            for key, most in (
                    ("node", lambda _: limits["per_node"]),
                    ("namespace", lambda _: limits["per_namespace"]),
                    ("workload", lambda ref: reference.max_unavailable(
                        replicas[ref], limits["migrating_per_workload"])
                        if replicas.get(ref) else 2)):
                counts: dict[str, int] = {}
                for job in running:
                    if job[key]:
                        counts[job[key]] = counts.get(job[key], 0) + 1
                limit_exceeded += sum(1 for group, c in counts.items()
                                      if c > most(group))

        sched = self.scheduler
        with sched.lock:
            off_reservation = on_source = 0
            for name in self.replacements:
                bound = sched.bound.get(name)
                if bound is None:
                    # not placed: the books' placed_share says so
                    continue
                info = self.reservation_of.get(bound.reservation or "")
                if info is None or bound.node != info[0]:
                    off_reservation += 1
                elif bound.node == info[1]:
                    on_source += 1
            still_held = sum(1 for pod in self.evicted
                             if pod in sched.bound or pod in sched.pending)
        reconciles = sum(1 for e in self.reconcile_log if e["allowed"])
        return {
            "victim_set_mismatch": victim_mismatch,
            "victims_off_hot_nodes": victim_not_hot,
            "arbitration_mismatch": arbitration_mismatch,
            "arbitration_limit_exceeded": limit_exceeded,
            "evicted_without_reservation": self.evicted_without_reservation,
            "replacement_off_reservation": off_reservation,
            "replacement_on_source": on_source,
            "evicted_pods_still_held": still_held,
            "reserve_rounds_beyond_one": max(
                0, self.reserve_rounds - reconciles),
        }

    def close(self) -> None:
        self.descheduler.stop()
        super().close()
