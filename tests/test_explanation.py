"""ScheduleExplanation persistence + workload auditor
(scheduler/explanation.py) vs frameworkext/schedule_diagnosis.go:44-108 and
frameworkext/workloadauditor/workload_auditor.go."""

import random

import jax.numpy as jnp
import numpy as np
import pytest

from koordinator_tpu import metrics
from koordinator_tpu.api.resources import NUM_RESOURCE_DIMS, resource_vector
from koordinator_tpu.ops.assignment import ScoringConfig
from koordinator_tpu.scheduler import ClusterSnapshot, NodeSpec, PodSpec, Scheduler
from koordinator_tpu.scheduler import explanation as explanation_mod
from koordinator_tpu.scheduler.diagnosis import PodDiagnosis
from koordinator_tpu.scheduler.explanation import (
    ExplanationStore,
    WorkloadAuditor,
)

R = NUM_RESOURCE_DIMS


def diag(**kw):
    defaults = dict(total_nodes=4, feasible_nodes=0,
                    insufficient_resources=4, usage_over_threshold=0,
                    affinity_mismatch=0, quota_rejected=False, invalid=0)
    defaults.update(kw)
    return PodDiagnosis(**defaults)


def test_async_record_drain_and_delete():
    store = ExplanationStore(clock=lambda: 42.0)
    store.record("p1", diag())
    assert store.get("p1") is None          # queued, not yet written
    assert store.drain() == 1
    exp = store.get("p1")
    assert exp.pod_name == "p1" and exp.update_time == 42.0
    assert "4 insufficient resources" in exp.reasons[0]
    store.delete("p1")
    assert store.get("p1") is None


def test_blocking_mode_writes_through():
    store = ExplanationStore(blocking=True)
    store.record("p1", diag())
    assert store.get("p1") is not None


def test_queue_bound_drops_instead_of_blocking():
    store = ExplanationStore(queue_size=2)
    for i in range(5):
        store.record(f"p{i}", diag())
    assert store.dropped == 3
    assert store.drain() == 2


def test_capacity_evicts_oldest():
    store = ExplanationStore(capacity=2, blocking=True)
    for i in range(3):
        store.record(f"p{i}", diag())
    assert store.get("p0") is None
    assert store.get("p1") is not None and store.get("p2") is not None


def test_preemption_nomination_lands_on_cr():
    store = ExplanationStore(blocking=True)
    store.record("p1", diag(preempt_node="n3", preempt_victims=["v1", "v2"]))
    exp = store.get("p1")
    assert "n3" in exp.node_offers
    assert "preempting [v1, v2]" in exp.node_offers["n3"]


def test_auditor_rings_and_transitions():
    t = [0.0]
    a = WorkloadAuditor(ring_size=4, clock=lambda: t[0])
    a.record_attempt("gang-a")
    a.record_attempt("gang-a")
    assert a.attempts("gang-a") == 2
    a.record_gating("p", True)
    a.record_gating("p", True)    # no transition -> no event
    a.record_gating("p", False)
    assert [e.record_type for e in a.events("p")] == ["Gated", "Gated"]
    assert [e.message for e in a.events("p")] == ["gated", "ungated"]
    for i in range(10):
        a.record("gang-a", "ScheduleFailed", f"m{i}")
    assert len(a.events("gang-a")) == 4   # ring bound
    assert [e.message for e in a.events("gang-a")] == ["m6", "m7", "m8", "m9"]
    a.delete("gang-a")
    assert a.attempts("gang-a") == 0 and a.events("gang-a") == []


def test_disabled_auditor_records_nothing():
    a = WorkloadAuditor(enabled=False)
    a.record_attempt("x")
    a.record("x", "ScheduleFailed")
    assert a.attempts("x") == 0 and a.events("x") == []


def test_scheduler_persists_and_clears_explanations():
    snap = ClusterSnapshot(capacity=16)
    snap.upsert_node(NodeSpec(
        name="n1", allocatable=resource_vector(cpu=4_000, memory=8_192),
        usage=np.zeros(R, np.int32)))
    cfg = ScoringConfig.default().replace(
        usage_thresholds=jnp.zeros(R, jnp.int32),
        estimator_defaults=jnp.zeros(R, jnp.int32))
    store = ExplanationStore(blocking=True)
    auditor = WorkloadAuditor()
    sched = Scheduler(snap, config=cfg, explanations=store, auditor=auditor)

    sched.enqueue(PodSpec(name="big",
                          requests=resource_vector(cpu=99_000, memory=1_024)))
    res = sched.schedule_round()
    assert "big" in res.failures
    exp = store.get("big")
    assert exp is not None and "available" in exp.reasons[0]
    assert auditor.attempts("big") == 1
    assert auditor.events("big")[-1].record_type == "ScheduleFailed"

    # shrink the pod and reschedule: explanation clears, success recorded
    sched.pending.pop("big")
    sched.enqueue(PodSpec(name="big",
                          requests=resource_vector(cpu=1_000, memory=1_024)))
    res = sched.schedule_round()
    assert res.assignments == {"big": "n1"}
    assert store.get("big") is None
    assert auditor.events("big")[-1].record_type == "ScheduleSuccess"


def test_delete_purges_queued_entry_too():
    # a bind between record() and drain() must not resurrect the failure
    store = ExplanationStore()
    store.record("p1", diag())
    store.delete("p1")       # bound before the worker drained
    assert store.drain() == 0
    assert store.get("p1") is None


# ---- the keyed, batched store against the scanning one (ISSUE 25) ----------


class _ScanningStore:
    """What ``ExplanationStore`` did before it kept an index, as plainly
    as it can be said: the queue a list that every delete scans, the
    store a dict in write order.  Entries are (name, update_time)."""

    def __init__(self, capacity, queue_size, blocking, clock):
        self.capacity, self.queue_size = capacity, queue_size
        self.blocking, self.clock = blocking, clock
        self.queue: list[tuple[str, float]] = []
        self.store: dict[str, tuple[str, float]] = {}
        self.dropped = 0

    def _write(self, entry):
        self.store.pop(entry[0], None)
        self.store[entry[0]] = entry
        while len(self.store) > self.capacity:
            del self.store[next(iter(self.store))]

    def record(self, name):
        entry = (name, self.clock())
        if self.blocking:
            self._write(entry)
        elif len(self.queue) >= self.queue_size:
            self.dropped += 1
        else:
            self.queue.append(entry)

    def delete(self, name):
        self.store.pop(name, None)
        self.queue = [e for e in self.queue if e[0] != name]

    def delete_many(self, names):
        for name in names:
            self.delete(name)

    def drain(self, max_items=None):
        n = 0
        while self.queue and (max_items is None or n < max_items):
            self._write(self.queue.pop(0))
            n += 1
        return n

    def get(self, name):
        return self.store.get(name)

    def list(self):
        return list(self.store.values())


def _ticking_clock():
    t = [0.0]

    def clock():
        t[0] += 1.0
        return t[0]
    return clock


def _seen(exp):
    return None if exp is None else (exp.pod_name, exp.update_time)


@pytest.mark.parametrize("seed", range(6))
@pytest.mark.parametrize("queue_size,capacity", [(3, 2), (5, 4), (8, 3)])
@pytest.mark.parametrize("blocking", [False, True])
def test_store_agrees_with_the_scanning_reference(
        blocking, queue_size, capacity, seed):
    rng = random.Random(seed * 1_000 + queue_size * 10 + capacity)
    pool = [f"p{i}" for i in range(6)]
    store = ExplanationStore(capacity=capacity, queue_size=queue_size,
                             blocking=blocking, clock=_ticking_clock())
    ref = _ScanningStore(capacity, queue_size, blocking, _ticking_clock())
    for step in range(400):
        op = rng.choice(("record", "record", "record", "delete",
                         "delete_many", "drain", "get", "list"))
        if op == "record":
            name = rng.choice(pool)
            store.record(name, diag())
            ref.record(name)
        elif op == "delete":
            name = rng.choice(pool)
            store.delete(name)
            ref.delete(name)
        elif op == "delete_many":
            # with repeats, and in an order of its own
            names = [rng.choice(pool) for _ in range(rng.randint(0, 4))]
            store.delete_many(iter(names))
            ref.delete_many(names)
        elif op == "drain":
            max_items = rng.choice((None, 0, 1, 2, 3))
            assert store.drain(max_items) == ref.drain(max_items), step
        elif op == "get":
            name = rng.choice(pool)
            assert _seen(store.get(name)) == ref.get(name), step
        else:
            assert [_seen(e) for e in store.list()] == ref.list(), step
        assert store.dropped == ref.dropped, step
        assert [_seen(e) for e in store._queue] == ref.queue, step
        counts: dict[str, int] = {}
        for name, _t in ref.queue:
            counts[name] = counts.get(name, 0) + 1
        assert store._queued == counts, step
    # what is still queued comes out in the same order
    assert store.drain() == ref.drain()
    assert [_seen(e) for e in store.list()] == ref.list()
    assert store._queued == {}


class _CountingEntry:
    """Stands in for ``ScheduleExplanation`` in the queue and counts who
    looks at an entry's ``pod_name``."""

    reads = 0

    def __init__(self, pod_name, **_fields):
        self._pod_name = pod_name

    @property
    def pod_name(self):
        _CountingEntry.reads += 1
        return self._pod_name


def test_delete_of_an_unqueued_name_never_walks_the_queue(monkeypatch):
    monkeypatch.setattr(explanation_mod, "ScheduleExplanation",
                        _CountingEntry)
    monkeypatch.setattr(_CountingEntry, "reads", 0)
    store = ExplanationStore()
    for i in range(store.queue_size - 2):
        store.record(f"q{i}", diag())
    store.record("twice", diag())
    store.record("twice", diag())
    store.record("over", diag())
    assert store.dropped == 1 and len(store._queue) == store.queue_size
    purged = metrics.explanation_queue_purged.value()

    for i in range(1_000):
        store.delete(f"never-failed-{i}")
    store.delete_many(f"never-failed-{i}" for i in range(1_000))
    store.delete_many(())
    assert _CountingEntry.reads == 0
    assert metrics.explanation_queue_purged.value() == purged

    store.delete("twice")
    assert metrics.explanation_queue_purged.value() == purged + 2
    # one pass for the whole batch, however many of its names are queued
    monkeypatch.setattr(_CountingEntry, "reads", 0)
    store.delete_many(["q1", "never-failed-0", "q2", "q1"])
    assert _CountingEntry.reads == store.queue_size - 2
    assert metrics.explanation_queue_purged.value() == purged + 4
    assert store.drain() == store.queue_size - 4


# ---- the auditor's batched calls against the per-key loop (ISSUE 25) -------


def _audit_state(auditor, keys):
    return {key: (auditor.attempts(key),
                  [(e.record_type, e.message) for e in auditor.events(key)])
            for key in keys}


@pytest.mark.parametrize("ring_size", [2, 4, 32])
def test_batched_audit_calls_agree_with_the_per_key_loop(ring_size):
    keys = ["gang-a", "p1", "p2", "gang-a", "p3", "p1", "gang-a"]
    pairs = [(key, f"n{i}") for i, key in enumerate(keys)]
    batched = WorkloadAuditor(ring_size=ring_size, clock=_ticking_clock())
    looped = WorkloadAuditor(ring_size=ring_size, clock=_ticking_clock())
    for _round in range(3):   # the third overflows every ring bound here
        batched.record_attempts(iter(keys))
        for key in keys:
            looped.record_attempt(key)
        batched.record_many("ScheduleSuccess", iter(pairs))
        for key, message in pairs:
            looped.record(key, "ScheduleSuccess", message)
        assert _audit_state(batched, keys) == _audit_state(looped, keys)
    assert batched.attempts("gang-a") == 9
    assert all(len(batched.events(key)) == min(ring_size, 6 * keys.count(key))
               for key in keys)
    # one instant per batched call
    stamps = {e.timestamp for e in batched.events("gang-a")[-3:]}
    assert len(stamps) == 1

    before = _audit_state(batched, keys)
    batched.record_attempts(())
    batched.record_many("ScheduleFailed", ())
    assert _audit_state(batched, keys) == before


def test_a_ring_of_size_zero_keeps_nothing():
    a = WorkloadAuditor(ring_size=0)
    a.record_attempts(["x"])
    a.record("x", "ScheduleFailed", "m")
    assert a.attempts("x") == 1 and a.events("x") == []


def test_disabled_auditor_records_no_batch():
    a = WorkloadAuditor(enabled=False)
    a.record_attempts(["x", "y"])
    a.record_many("ScheduleFailed", [("x", "m")])
    assert a.attempts("x") == 0 and a.events("x") == [] and not a._records


def test_binding_a_pod_with_a_queued_failure_moves_the_purge_counter():
    snap = ClusterSnapshot(capacity=16)
    snap.upsert_node(NodeSpec(
        name="n1", allocatable=resource_vector(cpu=4_000, memory=8_192),
        usage=np.zeros(R, np.int32)))
    cfg = ScoringConfig.default().replace(
        usage_thresholds=jnp.zeros(R, jnp.int32),
        estimator_defaults=jnp.zeros(R, jnp.int32))
    store = ExplanationStore()       # async, as koord-scheduler builds it
    sched = Scheduler(snap, config=cfg, explanations=store,
                      auditor=WorkloadAuditor())
    sched.enqueue(PodSpec(name="big",
                          requests=resource_vector(cpu=99_000, memory=1_024)))
    assert "big" in sched.schedule_round().failures
    assert store._queued == {"big": 1}
    purged = metrics.explanation_queue_purged.value()

    sched.pending.pop("big")
    sched.enqueue(PodSpec(name="big",
                          requests=resource_vector(cpu=1_000, memory=1_024)))
    sched.enqueue(PodSpec(name="small",
                          requests=resource_vector(cpu=500, memory=512)))
    assert set(sched.schedule_round().assignments) == {"big", "small"}
    assert metrics.explanation_queue_purged.value() == purged + 1
    assert store.drain() == 0 and store.get("big") is None
