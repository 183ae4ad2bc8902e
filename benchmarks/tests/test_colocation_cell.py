"""``spark_colocation_cycle``: four planted faults each come out not
correct, by the number that names them; the bytes of a reconcile from
shapes; the eight new readers on hand-written timeline docs
(``data/colo_span_docs.json``), a reduced trace and the counter.  The sound
dry run, the control and the broken timed path run on this cell through
``test_correct.py``; ``colo_fault_run.py`` plants a fault at the cell's own
size on the chip."""

from __future__ import annotations

import json
import os

import numpy as np
import pytest

from benchmarks import colocation_bytes, run
from benchmarks.context import Context
from benchmarks.layers import (
    colo_admit_ms_per_pod,
    colo_patches_per_tick,
    colo_push_ms_per_patch,
    colo_reconcile_device_ms,
    colo_reconcile_roofline,
    colo_records_ms_per_node,
    colo_tick_ms,
    colo_watch_ms_per_event,
)
from benchmarks.tests.test_correct import failing, last_line

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "spark_colocation_cycle"


# -- planted faults ------------------------------------------------------------

def patch_dropped(monkeypatch):
    """The first patch of every tick is stamped as synced and never
    pushed."""
    from koordinator_tpu.manager.colocation_loop import ColocationLoop

    real = ColocationLoop._push
    monkeypatch.setattr(ColocationLoop, "_push",
                        lambda self, patches, tracing:
                        real(self, patches[1:], tracing))


def tick_fed_the_previous_wave(monkeypatch):
    """The manager's watch applies each node's report one wave late."""
    from koordinator_tpu.manager.colocation_loop import ManagerSyncBinding

    real = ManagerSyncBinding.node_usage
    late: dict = {}

    def node_usage(self, entry, arrs):
        previous = late.get(entry["name"])
        late[entry["name"]] = (entry, arrs)
        if previous is not None:
            real(self, dict(previous[0], usage_time=entry.get("usage_time")),
                 previous[1])

    monkeypatch.setattr(ManagerSyncBinding, "node_usage", node_usage)


def time_gap_rule_off(monkeypatch):
    """A node at rest is never synced again: the sync rule is asked as if
    no time had passed since its last patch."""
    from koordinator_tpu.manager.noderesource_controller import (
        NodeResourceController,
    )

    real = NodeResourceController._sync_reason
    monkeypatch.setattr(
        NodeResourceController, "_sync_reason",
        lambda self, record, now, *rest:
        real(self, record, min(now, record.last_sync_time), *rest))


def be_pod_on_a_squeezed_node(monkeypatch):
    """The scheduler applies every patch at twice its batch allocatable,
    so a node the manager squeezed still takes BE pods."""
    from koordinator_tpu.api.resources import ResourceDim
    from koordinator_tpu.transport.deltasync import SchedulerBinding

    real = SchedulerBinding.node_alloc
    batch = [int(ResourceDim.BATCH_CPU), int(ResourceDim.BATCH_MEMORY)]

    def node_alloc(self, entry, arrs):
        allocatable = np.array(arrs["allocatable"], np.int32)
        allocatable[batch] *= 2
        real(self, entry, dict(arrs, allocatable=allocatable))

    monkeypatch.setattr(SchedulerBinding, "node_alloc", node_alloc)


FAULTS = {
    "patch_dropped": (patch_dropped, "patch_value_mismatch"),
    "tick_fed_the_previous_wave": (tick_fed_the_previous_wave,
                                   "patch_value_mismatch"),
    "time_gap_rule_off": (time_gap_rule_off, "patch_set_mismatch"),
    "be_pod_on_a_squeezed_node": (be_pod_on_a_squeezed_node,
                                  "bind_on_squeezed_node"),
}


def test_sound_dry_run_is_correct(capsys):
    result = last_line(capsys, run.main, CELL)
    assert result["correct"] is True, failing(result)
    assert result["metrics"]["placed_share"]["value"] == 100.0


@pytest.mark.parametrize("fault", FAULTS)
def test_planted_fault_is_not_correct(capsys, monkeypatch, fault):
    plant, number = FAULTS[fault]
    plant(monkeypatch)
    result = last_line(capsys, run.main, CELL)
    assert result["correct"] is False
    assert number in failing(result), failing(result)


# -- the yardstick's own arithmetic ----------------------------------------------

def test_colocation_bytes_is_a_pure_function_of_shapes_and_rises():
    # by hand: 9 input quantities of cpu and memory, 4 columns written
    assert colocation_bytes.least_bytes(10_240) == 10_240 * (18 + 4) * 4
    assert (colocation_bytes.least_bytes(20_480)
            == 2 * colocation_bytes.least_bytes(10_240))
    with pytest.raises(ValueError):
        colocation_bytes.least_bytes(0)


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "data", "colo_span_docs.json")) as f:
        return json.load(f)


def ctx_of(recorded, t_open=None, t_close=None, docs=None):
    return Context(
        timeline_docs=recorded["docs"] if docs is None else docs,
        t_open=recorded["t_open"] if t_open is None else t_open,
        t_close=recorded["t_close"] if t_close is None else t_close,
        rounds=[{}] * 2)


# by hand.  colo.tick: the one at [90, 92] lies before the window, the one
# over [119, 121] is cut in half: (2.0 + 3.0 + 1.0) s over 2.5 ticks.
# records: 0.205 s over 20,480 nodes.  push: 1.6 + 2.4 s over 5,000 patches.
# watch: 0.05 + 0.01 + 0.06 s over 22,480 deltas.  admit: 0.034 s over 3,400.
@pytest.mark.parametrize("reader,expected", [
    (colo_tick_ms, 2400.0),
    (colo_records_ms_per_node, 0.205e3 / 20_480),
    (colo_push_ms_per_patch, 0.8),
    (colo_watch_ms_per_event, 0.12e3 / 22_480),
    (colo_admit_ms_per_pod, 0.01),
])
def test_reader_on_the_recorded_docs(recorded, reader, expected):
    assert reader.read(ctx_of(recorded)) == pytest.approx(expected)


@pytest.mark.parametrize("reader", [
    colo_tick_ms, colo_records_ms_per_node, colo_push_ms_per_patch,
    colo_watch_ms_per_event, colo_admit_ms_per_pod])
def test_reader_reads_none_with_nothing_to_read(recorded, reader):
    assert reader.read(ctx_of(recorded, 200.0, 210.0)) is None
    assert reader.read(ctx_of(recorded, docs=[])) is None


class FakeTrace:
    """A reduced trace: one device, two ``colo_tick`` spans of which the
    second straddles the window's end."""

    window = (10.0, 20.0)
    busiest = "/device:TPU:0"
    host_spans = [("colo_tick", 11.0, 13.0), ("usage_wave", 13.0, 14.0),
                  ("colo_tick", 19.5, 21.0)]
    ops = {"/device:TPU:0": [("fusion.1", 11.1, 11.10002),
                              ("fusion.2", 11.10001, 11.10004),
                              ("while.1", 13.2, 13.5),
                              ("fusion.1", 19.99999, 20.00001)]}


def test_reconcile_readers_on_a_reduced_trace(recorded):
    ctx = ctx_of(recorded)
    ctx.trace = FakeTrace()
    ctx.shapes = {"nodes": 10_240, "dims": 10}
    ctx.peak = {"hbm_bytes_per_s": 819e9}
    # device time inside the spans: [11.1, 11.10004] and [19.99999, 20.0]
    assert colo_reconcile_device_ms.read(ctx) == pytest.approx(0.025)
    least = 2 * colocation_bytes.least_bytes(10_240)
    share = colo_reconcile_roofline.read(ctx)
    assert share == pytest.approx(100.0 * least / 819e9 / 5e-5)
    assert 0.0 < share < 100.0
    ctx.trace = None
    assert colo_reconcile_device_ms.read(ctx) is None
    assert colo_reconcile_roofline.read(ctx) is None


def test_patches_per_tick_reads_the_counter_between_spans():
    from benchmarks.spans import Spans
    from koordinator_tpu import metrics

    counter = metrics.colocation_patches_total
    saved = counter.value()
    spans = Spans(False)
    ctx = Context(spans=spans, t_open=0.0, t_close=float("inf"))
    assert colo_patches_per_tick.read(ctx) is None
    try:
        for patches in (2_000, 2_200, 2_400):
            with spans.span("colo_tick", patches_before=counter.value()):
                counter.inc(patches)
        assert colo_patches_per_tick.read(ctx) == pytest.approx(2_200.0)
        # a program that keeps no such counter: the span holds None
        bare = Spans(False)
        with bare.span("colo_tick", patches_before=None):
            pass
        assert colo_patches_per_tick.read(
            Context(spans=bare, t_open=0.0, t_close=float("inf"))) is None
    finally:
        counter.reset_for_tests()
        counter.inc(saved)
