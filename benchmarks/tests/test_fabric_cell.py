"""``fabric_gang_cycle``: the rehearsal on three seeds does the same work on
each (the same pods offered cycle by cycle), places every one of them in its
own round, and never leaves a rack half busy; the traffic's schedule fits by
the proof its note gives; a planted fault (the round's plans not charged in
turn) comes out not correct by its own numbers.  Then the four new readers
on hand-written timeline docs, the counter, a hand-made trace view and a
piece cut out of a chip run's trace of this cell."""

from __future__ import annotations

import json
import os

import pytest

from benchmarks import run, topo_bytes
from benchmarks.context import Context
from benchmarks.layers import (
    topo_plan_device_ms,
    topo_plan_fail_share,
    topo_plan_ms_per_gang,
    topo_plan_roofline,
)
from benchmarks.spans import Spans
from benchmarks.tests.test_correct import failing

CELL = "fabric_gang_cycle"
SEEDS = (11, 2**31 + 5, 987_654_321)


def traffic() -> dict:
    with open(os.path.join(run.ROOT, "benchmarks", "traffic",
                           "topo_gang_cycle.json")) as f:
        return json.load(f)


def config() -> dict:
    with open(os.path.join(run.ROOT, "benchmarks", "configs",
                           "gpu-fabric-3k.json")) as f:
        return json.load(f)


def steady_racks(schedule: list[dict], per_rack: int = 2) -> int:
    """Racks busy after a round's arrivals once every lifetime has run."""
    return sum(k["per_cycle"] * k["lifetime"] * k["nodes"] // per_rack
               for k in schedule)


def rehearse(capsys, seed: int) -> tuple[dict, dict]:
    rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds", "3",
                   "--trace", "0", "--cpu-dry-run"])
    assert rc == 0
    lines = capsys.readouterr().out.strip().splitlines()
    counts = [ln for ln in lines if ln.startswith("DRY_RUN COUNTS ")][-1]
    return (json.loads(lines[-1][len("DRY_RUN "):]),
            json.loads(counts[len("DRY_RUN COUNTS "):]))


@pytest.fixture(scope="module")
def rehearsals():
    return {}


@pytest.mark.parametrize("seed", SEEDS)
def test_rehearsal_places_everything_in_its_own_round(capsys, rehearsals,
                                                      seed):
    result, counts = rehearse(capsys, seed)
    rehearsals[seed] = counts
    assert result["correct"] is True, failing(result)
    assert result["metrics"]["placed_share"]["value"] == 100.0
    dry = traffic()["dry"]["schedule"]
    per_cycle = sum(k["per_cycle"] * k["nodes"] for k in dry)
    assert counts["offered_by_cycle"] == [per_cycle] * counts["cycles"]
    assert counts["bound_by_cycle"] == counts["offered_by_cycle"]
    assert max(counts["busy_racks_by_cycle"]) <= steady_racks(dry)
    assert counts["half_busy_racks"] == 0


def test_every_seed_does_the_same_work(rehearsals):
    if len(rehearsals) < len(SEEDS):
        pytest.skip("needs the three rehearsals of this module")
    by_seed = [(c["offered_by_cycle"], c["left_by_cycle"],
                c["busy_racks_by_cycle"]) for c in rehearsals.values()]
    assert all(work == by_seed[0] for work in by_seed)


def test_the_schedule_fits_by_the_proof():
    """After a round's arrivals 1,024 of 1,536 racks are busy; a
    pod-gathered gang of r racks is planned with at most 1,024 - r busy,
    which no state with fewer than r free racks in every pod allows."""
    cfg, mix = config(), traffic()
    for sizes, schedule in ((cfg["sizes"], mix["schedule"]),
                            (cfg["dry"], mix["dry"]["schedule"])):
        racks = sizes["pods"] * sizes["racks_per_pod"]
        busy = steady_racks(schedule, sizes["nodes_per_rack"])
        assert busy < racks
        for kind in schedule:
            r = kind["nodes"] // sizes["nodes_per_rack"]
            most_busy = busy - r
            if kind["gather"] == "pod":
                assert r <= sizes["racks_per_pod"]
                # no pod with r free racks: free <= pods (r - 1)
                assert racks - sizes["pods"] * (r - 1) > most_busy
            else:
                assert racks - most_busy >= max(r, 1)
    assert steady_racks(mix["schedule"]) == 1024
    assert sum(k["per_cycle"] for k in mix["schedule"]) == 30
    assert sum(k["per_cycle"] * k["nodes"] for k in mix["schedule"]) == 608


def test_plans_not_charged_in_turn_is_not_correct(capsys, monkeypatch):
    """The round's gangs planned each against the state the round began
    with: two of them get the same free racks and the second backs off."""
    import jax

    from koordinator_tpu.scheduler import solver_kit

    real = solver_kit.SolverKit.__init__

    def uncharged(self, *args, **kw):
        real(self, *args, **kw)
        self.topo_charge = lambda work, requests, plan, cfg: work

    monkeypatch.setattr(solver_kit.SolverKit, "__init__", uncharged)
    jax.clear_caches()
    result, _ = rehearse(capsys, 11)
    assert result["correct"] is False
    assert {"unplaced_after_round", "plan_mismatch"} <= failing(result)


# -- the readers ----------------------------------------------------------------

def seg(name, start, end, n):
    return {"name": name, "cause": "build_batch", "parent": "phase.BatchBuild",
            "thread": "t", "start": start, "end": end, "n": n,
            "busy_s": end - start}


DOCS = [{"start": 100.0, "segments": [
    seg("topo.plan", 1.0, 1.03, 30),
    seg("topo.prep", 1.0, 1.02, 30),
    seg("topo.plan", 2.0, 2.03, 30),
    seg("topo.plan", 50.0, 50.1, 30),      # after the window
]}]


class Trace:
    """The trace clock runs 1,000 s ahead of the host's; three ops inside
    the first plan span, one inside the second, one outside both."""

    window = (1100.0, 1110.0)
    busiest = "/device:TPU:0"
    ops = {busiest: [("fusion", 1101.000, 1101.004),
                     ("fusion.1", 1101.002, 1101.006),
                     ("while", 1101.010, 1101.012),
                     ("fusion", 1102.020, 1102.025),
                     ("fusion", 1103.000, 1103.500)]}


def ctx(trace=None, **more):
    spans = Spans(False)
    spans.records.append(("window", 100.0, 110.0, {}))
    return Context(timeline_docs=DOCS, t_open=100.0, t_close=110.0,
                   spans=spans, trace=trace, rounds=[],
                   peak={"hbm_bytes_per_s": 819e9}, **more)


def test_plan_time_is_busy_over_gangs_inside_the_window():
    assert topo_plan_ms_per_gang.read(ctx()) == pytest.approx(60.0 / 60)


def test_device_time_is_the_ops_union_inside_the_plan_spans():
    # 0.006 + 0.002 in the first span, 0.005 in the second
    assert topo_plan_device_ms.read(ctx(Trace())) == pytest.approx(
        (0.008 + 0.005) * 1e3 / 2)


def test_the_roofline_reads_the_tree_shape_off_the_solve_spans():
    c = ctx(Trace())
    assert topo_plan_roofline.read(c) is None          # no span, no shape
    c.spans.records.append(("solve_request", 101.5, 101.9,
                            {"topo_tree": [3072, 4, 4617]}))
    least = 60 * topo_bytes.least_bytes(3072, 4, 4617)
    assert topo_plan_roofline.read(c) == pytest.approx(
        100.0 * least / 819e9 / 0.013)
    # a parent's span carries None: nothing to read, no raise
    c.spans.records[-1] = ("solve_request", 101.5, 101.9,
                           {"topo_tree": None})
    assert topo_plan_roofline.read(c) is None


def test_device_readers_on_a_cut_chip_trace():
    """``data/fabric_trace.json``: the first 1,300 device ops of a traced
    chip run of this cell (``cut_trace.py``), the start of the window's
    first round, whose first ops are the gang plans' programs.  Timeline
    records laid on it by hand: a ``topo.plan`` over the first plans and
    one over the frames before the round, where the device ran nothing."""
    from benchmarks.context import TraceView

    with open(os.path.join(os.path.dirname(__file__), "data",
                           "fabric_trace.json")) as f:
        view = TraceView(json.load(f)["trace"])
    w0 = view.window[0]
    host = 100.0       # the window's start on the host's clock

    def rec(t0, t1, n):   # a record given on the trace's clock
        return seg("topo.plan", t0 - w0, t1 - w0, n)

    plans = (0.865, 0.900)
    docs = [{"start": host, "segments": [rec(*plans, 5),
                                         rec(0.500, 0.600, 5)]}]
    spans = Spans(False)
    spans.records.append(("window", host, host + 10.0, {}))
    spans.records.append(("solve_request", host + 0.81, host + 0.86,
                          {"topo_tree": [3072, 4, 4617]}))
    c = Context(timeline_docs=docs, t_open=host, t_close=host + 10.0,
                spans=spans, trace=view, rounds=[],
                peak={"hbm_bytes_per_s": 819e9})
    # the busy union inside the plans' span, counted here from the ops
    inside = sorted((max(s, plans[0]), min(e, plans[1]))
                    for _, s, e in view.ops[view.busiest]
                    if e > plans[0] and s < plans[1])
    busy, reach = 0.0, plans[0]
    for s, e in inside:
        if e > reach:
            busy += e - max(s, reach)
            reach = e
    assert busy > 0.001
    assert topo_plan_device_ms.read(c) == pytest.approx(busy * 1e3 / 2)
    least = 10 * topo_bytes.least_bytes(3072, 4, 4617)
    assert topo_plan_roofline.read(c) == pytest.approx(
        100.0 * least / 819e9 / busy)
    assert topo_plan_ms_per_gang.read(c) == pytest.approx(
        (0.035 + 0.100) * 1e3 / 10)


def test_fail_share_reads_the_counter_after_less_before(monkeypatch):
    from koordinator_tpu import metrics

    c = ctx()
    before = topo_plan_fail_share.outcomes()
    c.spans.records.append(("depart", 101.0, 101.1,
                            {"plans_before": before}))
    metrics.topology_plans.inc(58, labels={"outcome": "planned"})
    assert topo_plan_fail_share.read(c) == 0.0
    metrics.topology_plans.inc(2, labels={"outcome": "no_fit"})
    assert topo_plan_fail_share.read(c) == pytest.approx(100.0 * 2 / 60)
    monkeypatch.delattr(metrics, "topology_plans")
    assert topo_plan_fail_share.read(c) is None


def test_readers_return_none_for_a_program_without_the_plans():
    empty = Context(timeline_docs=[], t_open=0.0, t_close=1.0,
                    spans=Spans(False), trace=None, rounds=[])
    for reader in (topo_plan_ms_per_gang, topo_plan_device_ms,
                   topo_plan_fail_share, topo_plan_roofline):
        assert reader.read(empty) is None
    assert topo_plan_device_ms.read(Context(
        timeline_docs=[], t_open=0.0, t_close=1.0, spans=Spans(False),
        trace=Trace(), rounds=[])) is None
