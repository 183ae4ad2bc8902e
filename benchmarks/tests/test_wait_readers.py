"""The six readers of PR 34 (waits in a connection's queues, the live DELTA
fan-out, the pending queue): the four that read timeline docs on
hand-written ones (``data/wait_docs.json``: six docs, the first before the
window, the second and the last cut in half by its ends), the two that read
the journey ledger on a ledger fed by hand; each gives ``None``, and does
not raise, on a program that keeps no such record."""

from __future__ import annotations

import json
import os

import numpy as np
import pytest

from benchmarks.context import Context
from benchmarks.layers import (
    fanout_ms_per_event,
    inbox_wait_ms_per_frame,
    outbox_wait_ms_per_frame,
    pod_queue_wait_p95_ms,
    pod_sched_e2e_p95_ms,
    watch_send_lag_ms,
)

HERE = os.path.dirname(os.path.abspath(__file__))
DOC_READERS = (inbox_wait_ms_per_frame, outbox_wait_ms_per_frame,
               watch_send_lag_ms, fanout_ms_per_event)


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "data", "wait_docs.json")) as f:
        return json.load(f)


def ctx_of(recorded, t_open=None, t_close=None, docs=None):
    return Context(
        timeline_docs=recorded["docs"] if docs is None else docs,
        t_open=recorded["t_open"] if t_open is None else t_open,
        t_close=recorded["t_close"] if t_close is None else t_close)


# by hand.  inbox: half of the doc at [98, 102] (0.01 s, 50 frames), all of
# the one at [103, 113] (0.09 s, 900), half of the one at [119, 121] (0.002 s,
# 1 frame).  outbox: 0.005 + 0.045 s over 50 + 900 replies.  DELTA: 0.2 + 0.6 s
# over 20 + 60 items.  sync.frame: 0.02 s over 5,000 events and half of a run
# of 1,000 in 0.01 s; the run before the window counts nothing.
@pytest.mark.parametrize("reader,expected", [
    (inbox_wait_ms_per_frame, 102.0 / 951),
    (outbox_wait_ms_per_frame, 50.0 / 950),
    (watch_send_lag_ms, 10.0),
    (fanout_ms_per_event, 25.0 / 5_500),
])
def test_reader_on_the_recorded_docs(recorded, reader, expected):
    assert reader.read(ctx_of(recorded)) == pytest.approx(expected)


@pytest.mark.parametrize("reader", DOC_READERS)
def test_reader_reads_none_with_nothing_to_read(recorded, reader):
    assert reader.read(ctx_of(recorded, 200.0, 210.0)) is None
    assert reader.read(ctx_of(recorded, docs=[])) is None


@pytest.mark.parametrize("reader", DOC_READERS[:3])
def test_wait_reader_reads_none_on_docs_without_waits(recorded, reader):
    """The parent's docs: the same segments, no ``waits`` map (and, in the
    older hand-written files, no ``wall_s``)."""
    bare = [{k: v for k, v in doc.items() if k != "waits"}
            for doc in recorded["docs"]]
    assert reader.read(ctx_of(recorded, docs=bare)) is None
    with open(os.path.join(HERE, "data", "colo_span_docs.json")) as f:
        older = json.load(f)
    assert reader.read(ctx_of(older)) is None


def test_no_wait_name_is_a_segment_of_the_recorded_docs(recorded):
    names = {s["name"] for doc in recorded["docs"] for s in doc["segments"]}
    waits = {n for doc in recorded["docs"] for n in doc.get("waits", {})}
    assert waits and not names & waits


# -- the journey ledger, cut to the window ------------------------------------

def fed_ledger(monkeypatch, rounds):
    """A ledger of the program's own class put in its place, fed
    ``rounds`` = [(round start, [queue waits], solve_s, commit_s)]."""
    from koordinator_tpu import journey
    from koordinator_tpu.scheduler.snapshot import PodSpec

    ledger = journey.JourneyLedger()
    monkeypatch.setattr(journey, "LEDGER", ledger)
    for r, (start, waits, solve_s, commit_s) in enumerate(rounds):
        pods = [PodSpec(name=f"r{r}p{i}", requests=np.zeros(4, np.int32),
                        qos=i % 2) for i in range(len(waits))]
        for pod, wait in zip(pods, waits):
            ledger._pending[pod.name] = (0.0, 0.0, start - wait)
        ledger.record_bind_batch(
            "", pods, round_start_perf=start, commit_perf=start + solve_s,
            ack_perf=start + solve_s + commit_s)
    return ledger


def test_pod_readers_cut_the_ledger_to_the_window(monkeypatch):
    rng = np.random.default_rng(7)
    inside = [rng.uniform(0.01, 0.3, 300) for _ in range(4)]
    rounds = [(95.0, rng.uniform(1.0, 2.0, 300).tolist(), 0.1, 0.02)]
    rounds += [(101.0 + 4 * i, w.tolist(), 0.15, 0.03)
               for i, w in enumerate(inside)]
    rounds += [(125.0, rng.uniform(1.0, 2.0, 300).tolist(), 0.1, 0.02)]
    fed_ledger(monkeypatch, rounds)
    ctx = Context(t_open=100.0, t_close=120.0)
    waits = np.concatenate(inside)
    want = float(np.quantile(waits, 0.95)) * 1e3
    assert pod_queue_wait_p95_ms.read(ctx) == pytest.approx(want, rel=0.02)
    assert pod_sched_e2e_p95_ms.read(ctx) == pytest.approx(
        want + 180.0, rel=0.02)
    # no round committed in the window: nothing to read
    empty = Context(t_open=300.0, t_close=320.0)
    assert pod_queue_wait_p95_ms.read(empty) is None
    assert pod_sched_e2e_p95_ms.read(empty) is None


def test_pod_readers_read_none_on_a_ledger_that_cannot_be_cut(monkeypatch):
    """The parent's ledger: ``snapshot_doc`` takes a tenant and nothing
    else."""
    from koordinator_tpu import journey

    class Cumulative:
        def snapshot_doc(self, tenant=None):
            return {"series": []}

    monkeypatch.setattr(journey, "LEDGER", Cumulative())
    ctx = Context(t_open=100.0, t_close=120.0)
    assert pod_queue_wait_p95_ms.read(ctx) is None
    assert pod_sched_e2e_p95_ms.read(ctx) is None


def test_pod_readers_read_none_with_the_ledger_off(monkeypatch):
    from koordinator_tpu import journey

    monkeypatch.setattr(journey, "LEDGER",
                        journey.JourneyLedger(enabled=False))
    assert pod_queue_wait_p95_ms.read(
        Context(t_open=0.0, t_close=1e9)) is None
