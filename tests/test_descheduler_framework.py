"""Descheduler framework: profiles, evictor filter (PDB), evictor modes,
LowNodeLoad bridge, migration-backed eviction."""

import numpy as np
import pytest

from koordinator_tpu.api import extension as ext
from koordinator_tpu.api.resources import NUM_RESOURCE_DIMS, ResourceDim
from koordinator_tpu.descheduler.framework import (
    Descheduler, Evictor, EvictorFilter, MODE_DELETE, MODE_SOFT, PDB, PodInfo,
    Profile,
)
from koordinator_tpu.descheduler.migration import MigrationController
from koordinator_tpu.descheduler.plugins import (
    CustomPriorityPlugin, LowNodeLoadPlugin, migration_evict_fn,
)


def pod(uid, node="n0", priority=5500, **kw):
    return PodInfo(uid=uid, name=uid, namespace="default", node=node,
                   priority=priority, **kw)


class TestEvictorFilter:
    def test_daemonset_and_storage_guards(self):
        f = EvictorFilter()
        assert not f.filter(pod("a", is_daemonset=True))[0]
        assert not f.filter(pod("b", has_local_storage=True))[0]
        assert f.filter(pod("c"))[0]

    def test_priority_threshold(self):
        f = EvictorFilter(priority_threshold=9000)
        assert f.filter(pod("a", priority=5000))[0]
        assert not f.filter(pod("b", priority=9500))[0]

    def test_pdb_budget(self):
        f = EvictorFilter(pdbs=[PDB(selector={"app": "web"},
                                    disruptions_allowed=1)])
        p1 = pod("a", labels={"app": "web"})
        p2 = pod("b", labels={"app": "web"})
        assert f.filter(p1)[0]
        f.consume_budget(p1)
        ok, reason = f.filter(p2)
        assert not ok and "PDB" in reason

    def test_eviction_cost_annotation(self):
        f = EvictorFilter()
        p = pod("a", annotations={ext.ANNOTATION_EVICTION_COST: "-2147483648"})
        assert not f.filter(p)[0]


class TestEvictorModes:
    def test_delete_mode(self):
        deleted = []
        ev = Evictor(mode=MODE_DELETE, delete_fn=lambda p: deleted.append(p.uid) or True)
        assert ev.evict(pod("a"), "r")
        assert deleted == ["a"]

    def test_soft_mode_labels(self):
        labeled = {}
        ev = Evictor(mode=MODE_SOFT,
                     label_fn=lambda p, ls: labeled.update({p.uid: ls}) or True)
        ev.evict(pod("a"), "LowNodeLoad")
        assert labeled["a"][ext.LABEL_SOFT_EVICTION] == "LowNodeLoad"


class TestProfileRound:
    def test_round_limit_and_filters(self):
        pods = [pod(f"p{i}", priority=3500) for i in range(5)]
        plugin = CustomPriorityPlugin(priority_floor=5000)
        profile = Profile(
            name="default",
            deschedule_plugins=[plugin],
            max_evictions_per_round=2,
        )
        d = Descheduler([profile], pods_fn=lambda: pods)
        out = d.run_once()
        assert out["default"] == 2
        assert len(profile.evictor.evicted) == 2

    def test_tick_interval(self):
        from tests.test_koordlet_metrics import FakeClock

        clock = FakeClock()
        profile = Profile(name="p")
        d = Descheduler([profile], pods_fn=list, interval_seconds=120,
                        clock=clock)
        assert d.tick() is not None
        assert d.tick() is None
        clock.tick(121)
        assert d.tick() is not None


def hot_scheduler():
    """Four nodes of 10,000 milli-CPU, n0 at 90 % (over high 65 %) and the
    others at 20 %; ``victim`` runs on n0, ``keeper`` on n1."""
    from koordinator_tpu.api.resources import resource_vector
    from koordinator_tpu.scheduler.scheduler import BoundPod, Scheduler
    from koordinator_tpu.scheduler.snapshot import ClusterSnapshot, NodeSpec

    snap = ClusterSnapshot(capacity=4)
    for i in range(4):
        usage = np.zeros(NUM_RESOURCE_DIMS, np.int32)
        usage[ResourceDim.CPU] = 9_000 if i == 0 else 2_000
        snap.upsert_node(NodeSpec(
            name=f"n{i}", usage=usage,
            allocatable=resource_vector(cpu=10_000, memory=10_000)))
    sched = Scheduler(snap)
    for name, node, priority in (("victim", "n0", 3500),
                                 ("keeper", "n1", 9500)):
        sched.bound[name] = BoundPod(
            name=name, node=node, priority=priority,
            requests=resource_vector(cpu=500, memory=64))
    sched.set_pod_usage(["victim", "keeper"], np.stack(
        [resource_vector(cpu=3_000), resource_vector(cpu=500)]))
    return sched


class TestLowNodeLoadPlugin:
    def run_rounds(self, rounds=3):
        from koordinator_tpu.descheduler.plugins import bound_pods_fn

        sched = hot_scheduler()
        plugin = LowNodeLoadPlugin(scheduler=sched)
        profile = Profile(name="ln", balance_plugins=[plugin])
        d = Descheduler([profile], pods_fn=bound_pods_fn(sched))
        results = [d.run_once() for _ in range(rounds)]
        return results, profile

    def test_anomaly_gating_then_evict(self):
        results, profile = self.run_rounds(3)
        # rounds 1-2: anomaly counter below threshold (3) -> no eviction
        assert results[0]["ln"] == 0
        assert results[1]["ln"] == 0
        assert results[2]["ln"] == 1
        assert profile.evictor.evicted == [("victim", "LowNodeLoad")]


    def test_equally_cheap_pods_leave_in_the_order_of_their_names(self):
        """Two pods of one priority and one usage on the hot node, one
        eviction needed: the one whose name sorts first goes, wherever
        the two sit in the bound pods' columns."""
        from koordinator_tpu.api.resources import resource_vector
        from koordinator_tpu.descheduler.plugins import bound_pods_fn
        from koordinator_tpu.scheduler.scheduler import BoundPod

        for first, second in (("twin-z", "twin-a"), ("twin-a", "twin-z")):
            sched = hot_scheduler()
            for name in (first, second):    # column order = this order
                sched.bound[name] = BoundPod(
                    name=name, node="n0", priority=3000,
                    requests=resource_vector(cpu=500, memory=64))
            sched.set_pod_usage([first, second], np.stack(
                [resource_vector(cpu=2_600)] * 2))
            cols = sched.bound.columns
            assert cols.slot_of[first] < cols.slot_of[second]
            profile = Profile(name="ln", balance_plugins=[
                LowNodeLoadPlugin(scheduler=sched)])
            d = Descheduler([profile], pods_fn=bound_pods_fn(sched))
            for _ in range(3):
                d.run_once()
            # 9,000 - 2,600 = 6,400 is under the high quantity 6,500
            assert profile.evictor.evicted == [("twin-a", "LowNodeLoad")]


class TestMigrationSink:
    def test_eviction_creates_jobs(self):
        controller = MigrationController()
        ev = Evictor(evict_fn=migration_evict_fn(controller))
        profile = Profile(
            name="p",
            deschedule_plugins=[CustomPriorityPlugin(priority_floor=5000)],
            evictor=ev,
        )
        pods = [pod("a", priority=3500, owner="Deployment/web")]
        Descheduler([profile], pods_fn=lambda: pods).run_once()
        assert len(controller.jobs) == 1
        job = next(iter(controller.jobs.values()))
        assert job.pod == "a" and job.workload == "Deployment/web"
