"""``lownodeload_rebalance``: four planted faults each come out not correct,
by the number that names them; the bytes of a victim selection from shapes;
the new readers on hand-written timeline docs
(``data/rebalance_span_docs.json``).  The sound dry run, the control and the
broken timed path run on this cell through ``test_correct.py``."""

from __future__ import annotations

import json
import os

import numpy as np
import pytest

from benchmarks import run, victim_bytes
from benchmarks.context import Context
from benchmarks.layers import (
    desched_balance_ms,
    migrate_arbitrate_ms,
    migrate_evict_ms_per_pod,
    migrate_reserve_ms_per_job,
    reserve_rounds_per_reconcile,
    victim_select_device_ms,
    victim_select_roofline,
)
from benchmarks.tests.test_correct import failing, last_line

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "lownodeload_rebalance"


# -- planted faults ------------------------------------------------------------

def evict_without_reserving(monkeypatch):
    """Reservation-first skipped: every job is told it holds a reservation
    that was never made."""
    from koordinator_tpu.descheduler import plugins

    monkeypatch.setattr(
        plugins, "scheduler_reserve_many",
        lambda scheduler, ttl_sec=1800.0: lambda jobs: {
            job.name: f"migrate-{job.name}" for job in jobs})


def third_job_on_a_node(monkeypatch):
    """Arbitration lets one job more run than a node's limit allows."""
    from koordinator_tpu.descheduler.migration import MigrationController

    real = MigrationController.arbitrate

    def arbitrate(self):
        allowed = real(self)
        nodes = {job.node for job in allowed}
        extra = [job for job in self.pending()
                 if job not in allowed and job.node in nodes]
        return allowed + extra[:1]

    monkeypatch.setattr(MigrationController, "arbitrate", arbitrate)


def reservation_on_the_source(monkeypatch):
    """The replacement's capacity is reserved on the very node the pod is
    to leave, and the check that a migration moves the pod is gone."""
    from koordinator_tpu.descheduler import plugins
    from koordinator_tpu.scheduler.reservations import (
        OwnerMatcher,
        ReservationSpec,
    )

    def reserve_on_source(scheduler, ttl_sec=1800.0):
        def reserve_many(jobs):
            out = {}
            for job in jobs:
                bound = scheduler.bound.get(job.pod)
                if bound is None:
                    out[job.name] = None
                    continue
                name = f"migrate-{job.name}"
                scheduler.add_reservation(ReservationSpec(
                    name=name, requests=np.asarray(bound.requests),
                    owners=[OwnerMatcher(labels=dict(bound.labels))],
                    allocate_once=True, ttl_sec=ttl_sec, node=bound.node))
                out[job.name] = name
            scheduler.schedule_round()
            return out
        return reserve_many

    monkeypatch.setattr(plugins, "scheduler_reserve_many", reserve_on_source)


def victim_from_a_cool_node(monkeypatch):
    """The anomaly gate and the threshold are skipped for one node that is
    not over: its pods are walked too."""
    from koordinator_tpu.descheduler.lownodeload import SourceNodeSelector

    real = SourceNodeSelector.observe

    def observe(self, usage, capacity, node_valid):
        abnormal, handles = real(self, usage, capacity, node_valid)
        if abnormal.any():
            cool = int(np.flatnonzero(~abnormal & np.asarray(node_valid))[0])
            abnormal = abnormal.copy()
            abnormal[cool] = True
            # the walk stops at a node under its high quantity: lower it
            usage_, budget, high, high_quant, on_device = handles
            handles = (usage_, budget, high,
                       high_quant.at[cool].set(0),
                       on_device.at[cool].set(True))
        return abnormal, handles

    monkeypatch.setattr(SourceNodeSelector, "observe", observe)


FAULTS = {
    "evict_without_reserving": (evict_without_reserving,
                                "evicted_without_reservation"),
    "third_job_on_a_node": (third_job_on_a_node,
                            "arbitration_limit_exceeded"),
    "reservation_on_the_source": (reservation_on_the_source,
                                  "replacement_on_source"),
    "victim_from_a_cool_node": (victim_from_a_cool_node,
                                "victims_off_hot_nodes"),
}


@pytest.mark.parametrize("fault", FAULTS)
def test_planted_fault_is_not_correct(capsys, monkeypatch, fault):
    plant, number = FAULTS[fault]
    plant(monkeypatch)
    result = last_line(capsys, run.main, CELL)
    assert result["correct"] is False
    assert number in failing(result), failing(result)


# -- the yardstick's own arithmetic ----------------------------------------------

def test_victim_bytes_is_a_pure_function_of_shapes_and_rises():
    base = victim_bytes.least_bytes(4_000, 10_240, 10)
    # by hand: reads 4,000 x 13 + 10,240 x 20 int32, writes 4,000 flags
    assert base == 4 * (4_000 * 13 + 10_240 * 20 + 4_000)
    assert victim_bytes.least_bytes(8_000, 10_240, 10) > base
    assert victim_bytes.least_bytes(4_000, 20_480, 10) > base
    assert victim_bytes.least_bytes(0, 10_240, 10) == 4 * 10_240 * 20
    with pytest.raises(ValueError):
        victim_bytes.least_bytes(4_000, 0, 10)


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "data", "rebalance_span_docs.json")) as f:
        return json.load(f)


def ctx_of(recorded, t_open=None, t_close=None, docs=None):
    return Context(
        timeline_docs=recorded["docs"] if docs is None else docs,
        t_open=recorded["t_open"] if t_open is None else t_open,
        t_close=recorded["t_close"] if t_close is None else t_close,
        rounds=[{}] * 4)


# by hand.  desched.round: the run over [98, 99] lies before the window,
# the one at 125 after it; 0.2 + 0.4 s over 2 rounds.  arbitrate: 0.01 +
# 0.03 s over 2 reconciles.  reserve: 0.05 + 0.6 s over 1,000 jobs (a run
# cut by the round inside it keeps its ratio).  evict: 6.0 s over 1,500 pods.
@pytest.mark.parametrize("reader,expected", [
    (desched_balance_ms, 300.0),
    (migrate_arbitrate_ms, 20.0),
    (migrate_reserve_ms_per_job, 0.65),
    (migrate_evict_ms_per_pod, 4.0),
])
def test_reader_on_the_recorded_docs(recorded, reader, expected):
    assert reader.read(ctx_of(recorded)) == pytest.approx(expected)


@pytest.mark.parametrize("reader", [desched_balance_ms, migrate_arbitrate_ms,
                                    migrate_reserve_ms_per_job,
                                    migrate_evict_ms_per_pod])
def test_reader_reads_none_with_nothing_to_read(recorded, reader):
    assert reader.read(ctx_of(recorded, 200.0, 210.0)) is None
    assert reader.read(ctx_of(recorded, docs=[])) is None


class FakeTrace:
    """A reduced trace: one device, two ``desched_balance`` spans of which
    the second straddles the window's end."""

    window = (10.0, 20.0)
    busiest = "/device:TPU:0"
    host_spans = [("desched_balance", 11.0, 11.5), ("usage_wave", 12.0, 13.0),
                  ("desched_balance", 19.5, 20.5)]
    ops = {"/device:TPU:0": [("while.1", 11.1, 11.2), ("fusion.2", 11.15, 11.3),
                              ("while.1", 12.0, 12.5), ("while.1", 19.9, 20.2)]}


def test_victim_select_readers_on_a_reduced_trace(recorded):
    ctx = ctx_of(recorded)
    ctx.trace = FakeTrace()
    ctx.shapes = {"nodes": 10_240, "dims": 10}
    ctx.peak = {"hbm_bytes_per_s": 819e9}
    # device time inside the spans: [11.1, 11.3] and [19.9, 20.0]
    assert victim_select_device_ms.read(ctx) == pytest.approx(150.0)
    # 6,000 candidates over two rounds: 3,000 a round
    least = 2 * victim_bytes.least_bytes(3_000, 10_240, 10)
    assert victim_select_roofline.read(ctx) == pytest.approx(
        100.0 * least / 819e9 / 0.3)
    assert 0.0 < victim_select_roofline.read(ctx) < 100.0
    ctx.trace = None
    assert victim_select_device_ms.read(ctx) is None
    assert victim_select_roofline.read(ctx) is None


def test_reserve_rounds_per_reconcile_reads_the_counter_between_spans():
    from benchmarks.spans import Spans
    from koordinator_tpu import metrics

    counter = metrics.migration_reserve_rounds
    saved = counter.value()
    spans = Spans(False)
    ctx = Context(spans=spans, t_open=0.0, t_close=float("inf"))
    assert reserve_rounds_per_reconcile.read(ctx) is None
    try:
        for _ in range(3):
            with spans.span("migrate_reconcile",
                            reserve_rounds_before=counter.value()):
                counter.inc()
        assert reserve_rounds_per_reconcile.read(ctx) == pytest.approx(1.0)
        # a program that keeps no such counter: the span holds None
        bare = Spans(False)
        with bare.span("migrate_reconcile", reserve_rounds_before=None):
            pass
        assert reserve_rounds_per_reconcile.read(
            Context(spans=bare, t_open=0.0, t_close=float("inf"))) is None
    finally:
        counter.reset_for_tests()
        counter.inc(saved)
