"""``gpushare_cycle``: the sound rehearsal is correct; with DeviceShare's
Filter forced to yes nothing binds wrongly (the grant and the commit hold),
and with the parent's degrade rule put back on top the run comes out not
correct by ``bind_without_grant``; the four new readers on hand-written
timeline docs and the counter.  ``gpu_fault_run.py`` plants a fault at the
cell's own size on the chip."""

from __future__ import annotations

import pytest

from benchmarks import run
from benchmarks.context import Context
from benchmarks.layers import (
    dev_grant_ms_per_pod,
    dev_inventory_ms_per_event,
    dev_lost_race_share,
    dev_release_ms_per_pod,
)
from benchmarks.spans import Spans
from benchmarks.tests.test_correct import failing, last_line

CELL = "gpushare_cycle"


# -- planted faults ------------------------------------------------------------

def device_mask_forced_true(monkeypatch):
    """DeviceShare's Filter answers yes for every pod on every node, in
    feasibility, in the rounds and in the diagnosis.  The grant itself is
    still made per device and the commit still refuses a bind without one,
    so nothing binds wrongly; what goes is the diagnosis of the pods no
    device can hold."""
    import jax.numpy as jnp

    from koordinator_tpu.ops import deviceshare

    monkeypatch.setattr(
        deviceshare, "device_fit_pods",
        lambda dev, requests, free=None: jnp.ones(
            (requests.shape[0], dev.shape[0]), bool))
    monkeypatch.setattr(
        deviceshare, "candidate_device_fit",
        lambda dev, free, req, cand_node: jnp.ones(cand_node.shape, bool))


def degrade_rule_back(monkeypatch):
    """The parent's rule on top of the forced mask: a bind whose device
    grant fails at the commit stands, with no devices."""
    from koordinator_tpu.scheduler.scheduler import Scheduler

    device_mask_forced_true(monkeypatch)
    monkeypatch.setattr(Scheduler, "_unbind_for_devices",
                        lambda self, pod, node, result: None)


FAULTS = {
    "degrade_rule_back": (degrade_rule_back, "bind_without_grant"),
}


def test_sound_dry_run_is_correct(capsys):
    result = last_line(capsys, run.main, CELL)
    assert result["correct"] is True, failing(result)
    assert 50.0 < result["metrics"]["placed_share"]["value"] <= 100.0


def planted_run(capsys, monkeypatch, plant) -> dict:
    """The rehearsal with ``plant`` in the program.  The solve's programs
    were traced with the sound filter by the test above, in this process:
    they are traced again with the fault, and once more without it
    afterwards."""
    import jax

    plant(monkeypatch)
    jax.clear_caches()
    try:
        return last_line(capsys, run.main, CELL)
    finally:
        monkeypatch.undo()
        jax.clear_caches()


def test_the_forced_mask_alone_binds_nothing_wrongly(capsys, monkeypatch):
    """Two lines of defence stand behind the Filter: the grant is made per
    device inside the solve, and the commit unreserves a bind that has
    none.  So a Filter that says yes everywhere costs placements (pods
    proposed where no device fits), never the rule."""
    result = planted_run(capsys, monkeypatch, device_mask_forced_true)
    for number in ("bind_without_grant", "grant_invalid",
                   "device_overcommit_cells", "device_state_mismatch",
                   "standing_bound"):
        assert result["compared"][number]["value"] == 0, failing(result)


@pytest.mark.parametrize("fault", FAULTS)
def test_planted_fault_is_not_correct(capsys, monkeypatch, fault):
    plant, number = FAULTS[fault]
    result = planted_run(capsys, monkeypatch, plant)
    assert result["correct"] is False
    assert number in failing(result), failing(result)


# -- the readers ----------------------------------------------------------------

def seg(name, parent, start, end, n):
    return {"name": name, "cause": "x", "parent": parent, "thread": "t",
            "start": start, "end": end, "n": n, "busy_s": end - start}


DOCS = [{"start": 100.0, "segments": [
    seg("bind.devices", "phase.Bind", 1.0, 1.004, 400),
    seg("release.devices", "release.fine_grained", 2.0, 2.002, 500),
    seg("sync.node_devices", "sync.store", 3.0, 3.001, 2),
    seg("bind.devices", "phase.Bind", 50.0, 50.1, 1),     # after the window
]}]


def ctx(**more):
    return Context(timeline_docs=DOCS, t_open=100.0, t_close=110.0,
                   spans=Spans(False), **more)


def test_span_readers_divide_busy_by_members_inside_the_window():
    assert dev_grant_ms_per_pod.read(ctx()) == pytest.approx(4.0 / 400)
    assert dev_release_ms_per_pod.read(ctx()) == pytest.approx(2.0 / 500)
    assert dev_inventory_ms_per_event.read(ctx()) == pytest.approx(1.0 / 2)


def test_span_readers_return_none_for_a_program_without_the_spans():
    empty = Context(timeline_docs=[], t_open=0.0, t_close=1.0,
                    spans=Spans(False))
    for reader in (dev_grant_ms_per_pod, dev_release_ms_per_pod,
                   dev_inventory_ms_per_event, dev_lost_race_share):
        assert reader.read(empty) is None


def test_lost_race_share_reads_the_counter_after_less_before(monkeypatch):
    from koordinator_tpu import metrics

    c = ctx()
    before = dev_lost_race_share.outcomes()
    c.spans.records.append(("device_events", 101.0, 101.1,
                            {"outcomes_before": before}))
    metrics.deviceshare_grants.inc(97, labels={"outcome": "granted"})
    metrics.deviceshare_grants.inc(3, labels={"outcome": "lost_race"})
    assert dev_lost_race_share.read(c) == pytest.approx(3.0)
    # a program that keeps no such counter: nothing to read, no raise
    monkeypatch.delattr(metrics, "deviceshare_grants")
    assert dev_lost_race_share.read(c) is None
