"""The five readers of the program's own spans, on hand-written timeline
docs (``data/program_span_docs.json``): a run that straddles ``t_open`` is
cut pro rata, what lies after ``t_close`` is left out, an empty window and a
program that keeps no such records read ``None``."""

from __future__ import annotations

import json
import os

import pytest

from benchmarks import program_spans
from benchmarks.context import Context
from benchmarks.layers import (
    explain_store_ms,
    frame_handover_ms,
    ingest_ms_per_event,
    kit_load_s,
    release_ms_per_pod,
)

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "data", "program_span_docs.json")) as f:
        return json.load(f)


def ctx_of(recorded, t_open=None, t_close=None, docs=None):
    return Context(
        timeline_docs=recorded["docs"] if docs is None else docs,
        t_open=recorded["t_open"] if t_open is None else t_open,
        t_close=recorded["t_close"] if t_close is None else t_close,
        rounds=[{}] * recorded["rounds"])


def test_records_are_cut_to_the_window_pro_rata(recorded):
    recs = program_spans.records(ctx_of(recorded))
    frames = [r for r in recs if r["name"] == "rpc.STATE_PUSH"]
    # the 10-frame run over [99, 101] is half inside; the 100 frames
    # after t_close are not there
    assert sorted(r["n"] for r in frames) == [5.0, 500]
    assert sorted(r["busy_s"] for r in frames) == [0.8, 2.0]


# by hand.  ingest: roots are the server's frames (0.8 s of 5 + 2.0 s of
# 500) and the in-process store (0.5 s of 100), not the store under a
# frame: 3.3 s / 605.  handover: waits under STATE_PUSH calls 0.9 + 2.25
# less the server's 0.8 + 2.0, over 505 frames; the wait for the solve is
# not in it.  release: 1.5 + 0.25 s over 500 pods.  explain store:
# 0.4 + 0.1 s over 2 rounds.
@pytest.mark.parametrize("reader,expected", [
    (ingest_ms_per_event, 3.3e3 / 605),
    (frame_handover_ms, 0.35e3 / 505),
    (release_ms_per_pod, 3.5),
    (explain_store_ms, 250.0),
])
def test_reader_on_the_recorded_docs(recorded, reader, expected):
    assert reader.read(ctx_of(recorded)) == pytest.approx(expected)


@pytest.mark.parametrize("reader", [ingest_ms_per_event, frame_handover_ms,
                                    release_ms_per_pod, explain_store_ms])
def test_reader_reads_none_with_nothing_to_read(recorded, reader):
    # a window no doc overlaps
    assert reader.read(ctx_of(recorded, 200.0, 210.0)) is None
    # no docs at all
    assert reader.read(ctx_of(recorded, docs=[])) is None
    # a program whose segments carry no members (the parent's shape)
    bare = [dict(d, segments=[{k: v for k, v in s.items()
                               if k not in ("n", "busy_s", "parent",
                                            "thread")}
                              for s in d["segments"]])
            for d in recorded["docs"]]
    assert reader.read(ctx_of(recorded, docs=bare)) is None


def test_frame_handover_needs_the_client_in_this_process(recorded):
    served_only = [dict(d, segments=[s for s in d["segments"]
                                     if s["cause"] != "rpc_client"])
                   for d in recorded["docs"]]
    assert frame_handover_ms.read(ctx_of(recorded, docs=served_only)) is None
    assert ingest_ms_per_event.read(
        ctx_of(recorded, docs=served_only)) == pytest.approx(3.3e3 / 605)


def test_explain_store_needs_a_round(recorded):
    ctx = ctx_of(recorded)
    ctx.rounds = []
    assert explain_store_ms.read(ctx) is None


def test_kit_load_sums_the_counter_over_every_fn(recorded):
    from koordinator_tpu import metrics

    counter = metrics.solver_load_seconds
    saved = counter.items()
    counter.reset_for_tests()
    try:
        assert kit_load_s.read(ctx_of(recorded)) is None
        counter.inc(1.5, labels={"fn": "gang_assign"})
        counter.inc(2.0, labels={"fn": "select_candidates"})
        counter.inc(0.25, labels={"fn": "gang_assign"})
        assert kit_load_s.read(ctx_of(recorded)) == pytest.approx(3.75)
    finally:
        counter.reset_for_tests()
        for labels, value in saved:
            counter.inc(value, labels=labels)
