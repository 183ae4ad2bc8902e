"""Gangs and an elastic-quota tree, assembled in-process.

Gang records and the quota tree have no wire kind today, so this deployment
assembles ``Scheduler`` + ``StateSyncService`` + ``SchedulerBinding`` the way
``chip_smoke.py``'s second leg and ``tools/loadgen.py`` do, and its round is
``Scheduler.schedule_round()``.  A wave is ``gangs`` gangs of ``gang_size``
(one member of every 16th gang fits nowhere, so that gang must stay wholly
unbound) plus ``quota_pods`` pods dealt over ``quota_leaves`` leaves whose
odd ones are tight.

A 1,024-node cluster holds only some seconds of such work, so a run replays
the same waves on several clusters, one after the other: every cluster is a
``Scheduler`` of its own over the same nodes, empty when its turn comes, and
all share one ``SolverKit`` (the jitted entries and their compiled programs
are the kit's).  The first cluster is drained in set-up; as it sees the same
names and the same numbers as the measured ones, every program and every
pod bucket the window meets is compiled or loaded there.  In the books a
cluster's pods, nodes, gangs and quotas carry the cluster's prefix.
"""

from __future__ import annotations

import shutil
import time

import numpy as np

from benchmarks.books import Books
from benchmarks.deployments import served_socket
from benchmarks.reference import checks, generators


class Cluster:
    """One scheduler over the configuration's nodes, with its sync service
    and quota tree; ``scope`` is what it was offered, under the books'
    names."""

    def __init__(self, prefix: str, scheduler, sync, tree, quota_max):
        self.prefix, self.scheduler = prefix, scheduler
        self.sync, self.tree, self.quota_max = sync, tree, quota_max
        self.scope: set[str] = set()


class Deployment(served_socket.Deployment):
    FULL_PATHS = ("full_gang",)

    def __init__(self, config: dict, sizes: dict, seed: int, run_dir: str):
        from koordinator_tpu.scheduler.solver_kit import SolverKit

        self.config, self.sizes = config, sizes
        self.dims, self.qos = config["resource_dims"], config["qos"]
        # every seed gets the same nodes and pods (drawn from the
        # configuration's ``values_seed``) in another order (drawn from
        # ``--seed``): which pods a tight leaf rejects follows from their
        # sizes, so sizes drawn from the seed would change the work
        self.values = np.random.default_rng(config["values_seed"])
        self.rng = np.random.default_rng(seed)
        self.books = Books(self.dims["count"])
        self.run_dir = run_dir
        self.client = None
        self.wave_no = 0
        self.members: dict[str, list[str]] = {}
        self.impossible: set[str] = set()
        self.pod_quota: dict[str, str] = {}
        n = sizes["nodes"]
        alloc, usage = generators.make_nodes(self.values, n, self.dims)
        order = self.rng.permutation(n)
        self.alloc, self.usage = alloc[order], usage[order]
        self.names = [f"g{i}" for i in range(n)]
        self.kit = SolverKit()
        self.clusters: list[Cluster] = []
        self.active: Cluster | None = None

    # -- set-up -------------------------------------------------------------

    def _cluster(self, prefix: str) -> Cluster:
        from koordinator_tpu.quota.tree import UNBOUNDED, QuotaTree
        from koordinator_tpu.scheduler import ClusterSnapshot, Scheduler
        from koordinator_tpu.transport.deltasync import (
            SchedulerBinding,
            StateSyncService,
        )

        total = self.alloc.sum(axis=0, dtype=np.int64)
        leaves = self.sizes["quota_leaves"]
        per_leaf = self.sizes["quota_pods"] // leaves
        tree, quota_max = QuotaTree(total), {}
        for q in range(leaves):
            share = 4 if q % 2 else 1   # tight leaves admit about a quarter
            mx = np.full(total.shape, UNBOUNDED, np.int64)
            # assumed: maxima scale with the wave count, so the tight
            # leaves end the replay having admitted about a quarter
            cap = per_leaf * 2_050 * self.sizes["quota_waves"] // share
            mx[self.dims["cpu"]] = mx[self.dims["batch_cpu"]] = cap
            tree.add(f"q{q}", min=np.zeros_like(total), max=mx)
            quota_max[f"q{q}"] = mx
        scheduler = Scheduler(ClusterSnapshot(capacity=len(self.names)),
                              quota_tree=tree, solver_kit=self.kit)
        sync = StateSyncService()
        sync.attach_binding(SchedulerBinding(scheduler))
        for i, name in enumerate(self.names):
            sync.upsert_node(name, self.alloc[i], usage=self.usage[i])
        return Cluster(prefix, scheduler, sync, tree, quota_max)

    def load_nodes(self, replays: int = 1) -> None:
        """``replays`` measured clusters, all loaded, and in the books one
        node table that holds every cluster's nodes under its prefix."""
        self.clusters = [self._cluster(f"c{i}/") for i in range(replays)]
        self.books.set_nodes(
            [c.prefix + name for c in self.clusters for name in self.names],
            np.tile(self.alloc, (replays, 1)),
            np.tile(self.usage, (replays, 1)))
        self.active = self.clusters[0]

    def next_cluster(self) -> None:
        self.active = self.clusters[self.clusters.index(self.active) + 1]

    def standing(self) -> list[tuple]:
        return []

    def wave(self, n: int | None = None) -> list[tuple]:
        s, w = self.sizes, self.wave_no
        self.wave_no += 1
        size, leaves = s["gang_size"], s["quota_leaves"]
        g_req, g_prio, g_qos = generators.make_pods(
            self.values, s["gangs"] * size, self.dims, self.qos)
        whale = generators.whale_request(self.dims)
        pods = []
        for g in range(s["gangs"]):
            gang = f"w{w}-gang{g}"
            for m in range(size):
                i = g * size + m
                impossible = g % 16 == 0 and m == 0
                pods.append((f"{gang}-m{m}",
                             whale if impossible else g_req[i],
                             int(g_prio[i]), int(g_qos[i]),
                             {"gang": gang, "impossible": impossible}))
        q_req, q_prio, q_qos = generators.make_pods(
            self.values, s["quota_pods"], self.dims, self.qos)
        for i in range(s["quota_pods"]):
            pods.append((f"w{w}-qp{i}", q_req[i], int(q_prio[i]),
                         int(q_qos[i]), {"quota": f"q{i % leaves}"}))
        return [pods[i] for i in self.rng.permutation(len(pods))]

    def warm_up(self, params: dict, plan: list | None = None) -> None:
        """Rehearse the whole plan on one more cluster, which sees the same
        names and numbers as each measured one: every program the window
        will run, also those whose shape follows from how many pods a round
        leaves unplaced, is compiled or loaded here."""
        twin = self._cluster("warm/")
        try:
            for i, pods in enumerate(plan):
                self._arrive(twin, pods)
                t0 = time.perf_counter()
                self._round(twin)
                if i == 0:
                    self.first_round_s = time.perf_counter() - t0
        finally:
            twin.scheduler.stop()

    # -- arrivals and rounds --------------------------------------------------

    def _arrive(self, cluster: Cluster, pods: list[tuple]) -> None:
        from koordinator_tpu.scheduler.scheduler import GangRecord

        size = self.sizes["gang_size"]
        for gang in dict.fromkeys(extra["gang"] for *_, extra in pods
                                  if "gang" in extra):
            cluster.scheduler.register_gang(GangRecord(name=gang,
                                                       min_member=size))
        for name, request, priority, qos, extra in pods:
            kwargs = {k: extra[k] for k in ("gang", "quota") if k in extra}
            cluster.sync.add_pod(name, request, priority=priority, qos=qos,
                                 **kwargs)

    def _round(self, cluster: Cluster):
        import jax

        with cluster.scheduler.lock:
            result = cluster.scheduler.schedule_round()
        jax.block_until_ready(cluster.scheduler.snapshot.state)
        return result

    def offer(self, pods: list[tuple], counts: bool = True) -> None:
        cluster, books = self.active, self.books
        prefix = cluster.prefix
        for name, request, _priority, _qos, extra in pods:
            books.offer(prefix + name, request, counts)
            cluster.scope.add(prefix + name)
            if "gang" in extra:
                gang = prefix + extra["gang"]
                self.members.setdefault(gang, []).append(prefix + name)
                if extra["impossible"]:
                    self.impossible.add(gang)
            else:
                self.pod_quota[prefix + name] = prefix + extra["quota"]
        self._arrive(cluster, pods)

    def push(self, doc, arrays=None):
        raise NotImplementedError("this deployment has no socket")

    def solve(self) -> int:
        cluster = self.active
        prefix = cluster.prefix
        result = self._round(cluster)
        return self.books.record_round({
            "assignments": {prefix + pod: prefix + node
                            for pod, node in result.assignments.items()},
            "failures": {prefix + pod: why
                         for pod, why in result.failures.items()}},
            scope=cluster.scope)

    @property
    def round_seq(self) -> int:
        return sum(c.scheduler.round_seq for c in self.clusters)

    def flight_records(self, after_round: int) -> list[dict]:
        """The measured clusters' records, in the order the window drove
        them; ``round`` counts on through the clusters."""
        docs = [r.to_doc() for c in self.clusters
                for r in list(c.scheduler.flight_recorder.records)]
        for i, doc in enumerate(docs):
            doc["round"] = i + 1
        return docs[after_round:]

    # -- after the window ---------------------------------------------------

    def held(self) -> dict:
        held: dict = {}
        for cluster in self.clusters:
            for key, part in served_socket.held_by(
                    cluster.scheduler).items():
                if isinstance(part, dict):
                    part = {cluster.prefix + k: (
                        cluster.prefix + v if key == "bound" else v)
                        for k, v in part.items()}
                    held.setdefault(key, {}).update(part)
                else:
                    held.setdefault(key, set()).update(
                        cluster.prefix + p for p in part)
        return held

    def verify(self) -> dict[str, int]:
        numbers = self.books.verify(self.held())
        bound, requests = self.books.bound, self.books.requests
        partial, _whole = checks.partial_gangs(
            self.members, self.sizes["gang_size"], bound)
        numbers["partial_gangs"] = partial
        numbers["impossible_gangs_bound"] = sum(
            any(p in bound for p in self.members[g]) for g in self.impossible)
        maxima, runtime = {}, {}
        for cluster in self.clusters:
            for leaf, mx in cluster.quota_max.items():
                maxima[cluster.prefix + leaf] = mx
                runtime[cluster.prefix + leaf] = np.where(
                    mx >= 0, cluster.tree.runtime_of(leaf), -1)
        numbers["quotas_over_max"] = checks.quota_over(
            self.pod_quota, requests, bound, maxima)
        numbers["quotas_over_runtime"] = checks.quota_over(
            self.pod_quota, requests, bound, runtime)
        return numbers

    def close(self) -> None:
        for cluster in self.clusters:
            cluster.scheduler.stop()
        shutil.rmtree(self.run_dir, ignore_errors=True)
