#!/usr/bin/env python
"""Soak verdict: drive a seeded churn soak, print the per-series trend
table joined to flight records and SLO breaches, fail on a leak.

The steady-state observatory's operator surface (ISSUE 9): replay a
deterministic :mod:`loadgen` trace against the assembled control plane
(scheduler sidecar + manager + feeder over real sockets), sample the
whole run through the shared SLO/trend MetricCache, and turn the run
into ONE verdict document:

- a per-series table — fitted slope, growth, r2, verdict
  (steady/drifting/leaking) for every watched series (RSS, fds,
  threads, alloc blocks, gc, queue depth, deltasync backlog, device
  bytes);
- the SLO join — per-SLO breach counts and peak burn from the same run;
- the flight-record join — for every non-steady series, the slowest
  and any dumped rounds inside the soak window, so "threads are
  leaking" arrives WITH "and round 4812 was the slow degraded one";
- hard bounds — deltasync backlog peak and degraded-mode state.

Exit status: 0 only when the verdict is green (no leaking, no
drifting, no live SLO breach, not degraded, backlog bounded).
``tools/soak.sh`` runs this under ``SOAK_LOADGEN=1`` and fails the
soak tally on a red verdict.

Self-test: ``--inject-leak thread`` (a toy service leaking a parked
thread per cycle) and ``--inject-leak queue`` (completions dropped,
rounds starved) must BOTH turn the verdict red — a leak detector that
never fires on a real leak is a rubber stamp.

    python tools/soak_report.py                       # smoke scale
    python tools/soak_report.py --nodes 10000 --duration 1800 \
        --time-scale 1                                # the real soak
    python tools/soak_report.py --inject-leak thread  # must go red
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.abspath(__file__)), ".."))

import loadgen  # noqa: E402


def _fmt_rate(doc: dict) -> str:
    rate = doc.get("rate_per_hour")
    if rate is None:
        return "-"
    for unit, div in (("G", 1e9), ("M", 1e6), ("k", 1e3)):
        if abs(rate) >= div:
            return f"{rate / div:+.2f}{unit}/h"
    return f"{rate:+.2f}/h"


#: attribution-quality bar (ISSUE 19): a soak whose timeline cannot
#: name this fraction of host wall time is flying on a rotten
#: instrument — the host-wait numbers the turbo work is judged by
#: would be unfalsifiable, so the verdict goes RED
UNATTRIBUTED_RED_FRACTION = 0.05


def host_wait_attribution(cycle_docs: list[dict], top: int = 4) -> dict:
    """Aggregate ``/debug/timeline`` round and cycle docs into the
    verdict's host-wait section: per-tenant top causes by attributed seconds
    (tenant-tagged segments; the untenanted scheduler's segments land
    under ``-``) and the WALL-WEIGHTED unattributed residual across
    cycles.  Wall-weighted, not a plain mean of per-cycle fractions:
    a degenerate sub-millisecond cycle (an empty round) is ~all
    residual by construction and would swamp a plain mean while
    representing no wall time anyone waits on."""
    per_tenant: dict[str, dict[str, float]] = {}
    resid_s = 0.0
    wall_s = 0.0
    # the wall between rounds (mode "ingest") is not judged: what no
    # span covers there is the program standing idle, not a residual
    cycle_docs = [c for c in cycle_docs if c.get("mode") != "ingest"]
    for cyc in cycle_docs:
        wall = float(cyc.get("wall_s", 0.0))
        wall_s += wall
        resid_s += float(cyc.get("unattributed_fraction", 0.0)) * wall
        for seg in cyc.get("segments", []):
            tenant = seg.get("tenant") or "-"
            causes = per_tenant.setdefault(tenant, {})
            # a run of back-to-back spans is one segment: its busy time,
            # not its extent (older docs carry no busy_s)
            dur = float(seg.get("busy_s",
                                float(seg["end"]) - float(seg["start"])))
            causes[seg["cause"]] = causes.get(seg["cause"], 0.0) + dur
    mean_resid = (resid_s / wall_s) if wall_s > 0 else 0.0
    return {
        "cycles": len(cycle_docs),
        "tenants": {
            t: [[c, round(s, 6)] for c, s in
                sorted(causes.items(), key=lambda kv: -kv[1])[:top]]
            for t, causes in sorted(per_tenant.items())},
        "unattributed_wall_fraction": round(mean_resid, 6),
        "unattributed_ok": mean_resid <= UNATTRIBUTED_RED_FRACTION,
    }


def attach_host_wait(verdict: dict, timeline_body: dict) -> dict:
    """Fold the host-wait attribution table into the verdict.  An
    armed recorder whose cycles carry a mean unattributed residual
    above the bar flips the verdict RED — the attribution the perf
    work steers by must stay accountable.  A disarmed recorder (kill
    switch) or a run with no reconstructed cycles attaches the empty
    table without judging it."""
    hw = host_wait_attribution(timeline_body.get("cycles", []))
    verdict["host_wait"] = hw
    if (timeline_body.get("enabled") and hw["cycles"]
            and not hw["unattributed_ok"]):
        verdict["green"] = False
        hw["red_reason"] = (
            f"mean unattributed host-wait residual "
            f"{hw['unattributed_wall_fraction']:.3f} > "
            f"{UNATTRIBUTED_RED_FRACTION:.2f}")
    return hw


def attach_journey(verdict: dict) -> dict:
    """Fold the pod-journey ledger's latency table into the verdict —
    the same merge primitive tools/latency_report.py applies to fleet
    JSONL snapshots, run over this process's own sketch rows (ISSUE 20).
    A disabled ledger (kill switch) attaches the empty table without
    judging it; the journey table is evidence, not a gate."""
    import latency_report

    from koordinator_tpu import journey

    rows = (journey.LEDGER.snapshot_doc()["series"]
            if journey.LEDGER.enabled else [])
    table = latency_report.journey_table(rows)
    table["enabled"] = journey.LEDGER.enabled
    verdict["journey"] = table
    return table


def print_report(verdict: dict, harness) -> None:
    trend = verdict["trend"]
    print("== steady-state verdict "
          f"(window {trend['window_s']:.0f}s, "
          f"{verdict['rounds']} rounds, "
          f"{verdict['events_applied']} events, "
          f"{verdict['push_errors']} push errors)")
    print(f"{'series':<44} {'verdict':<9} {'slope':>11} "
          f"{'growth':>12} {'r2':>5} {'n':>5}")
    for doc in trend["series"]:
        labels = ",".join(f"{k}={v}" for k, v in doc["labels"].items())
        name = doc["series"] + (f"{{{labels}}}" if labels else "")
        growth = doc.get("growth")
        print(f"{name:<44} {doc['verdict']:<9} {_fmt_rate(doc):>11} "
              f"{(f'{growth:+.3g}' if growth is not None else '-'):>12} "
              f"{doc.get('r2', 0.0):>5.2f} "
              f"{doc.get('samples', 0):>5}")
    print(f"-- SLO: breached now={verdict['slo_breached'] or 'none'}")
    for name, s in verdict["slo"].items():
        print(f"   {name:<28} breaches={s['breaches_total']} "
              f"peak burn fast={s['peak_burn']['fast']:.2f} "
              f"slow={s['peak_burn']['slow']:.2f}")
    fl = verdict["flight"]
    print(f"-- flight recorder: {fl['records']} records, "
          f"{fl['dumps']} dumps, {fl['overwrites']} overwritten "
          f"(ring {harness.scheduler.flight_recorder.capacity})")
    tenants = verdict.get("tenants")
    if tenants:
        cycle = verdict.get("cycle", {})
        print(f"-- tenants ({len(tenants)}; cycle mode="
              f"{cycle.get('mode', '?')} host-wait="
              f"{cycle.get('host_wait_fraction', 0.0):.3f})")
        print(f"   {'tenant':<8} {'w':>4} {'pending':>8} {'bound':>7} "
              f"{'rounds':>7} {'admitted':>9} {'degraded':>9} "
              f"{'dumps':>6}")
        for name, t in sorted(tenants.items()):
            print(f"   {name:<8} {t['weight']:>4.1f} "
                  f"{t['pending']:>8} {t['bound']:>7} "
                  f"{t['rounds']:>7} {t['admitted_total']:>9} "
                  f"{str(t['degraded']):>9} {t['flight_dumps']:>6}")
    jt = verdict.get("journey")
    if jt and jt["series"]:
        import latency_report

        e2e = [r for r in jt["series"] if r["stage"] == "e2e"]
        print(f"-- pod journey ({len(e2e)} tenant x qos series, "
              f"alpha={jt['alpha']:.0%}; e2e p99 then stage split)")
        latency_report.print_table(jt)
    hw = verdict.get("host_wait")
    if hw and hw["cycles"]:
        print(f"-- host-wait attribution ({hw['cycles']} cycles; "
              f"unattributed wall="
              f"{hw['unattributed_wall_fraction']:.3f} "
              f"bar={UNATTRIBUTED_RED_FRACTION:.2f} "
              f"{'ok' if hw['unattributed_ok'] else 'RED'})")
        for tenant, causes in hw["tenants"].items():
            row = "  ".join(f"{c}={s:.3f}s" for c, s in causes)
            print(f"   {tenant:<8} {row}")
    # the join: every non-steady series arrives WITH the rounds that
    # overlapped it — dumped (slow/degraded/slo) rounds first, else the
    # slowest — so the leak verdict and its "what was happening" flight
    # evidence are one artifact
    flagged = trend["leaking"] + trend["drifting"]
    if flagged:
        rec = harness.scheduler.flight_recorder
        dumped = [r for r in rec.snapshot(8) if r.get("dump_reason")]
        join = dumped or ([rec.slowest()] if rec.slowest() else [])
        print(f"-- flagged series: {flagged}")
        for r in join[:4]:
            print(f"   round {r['round']} trace={r['trace_id'][:12]} "
                  f"dur={r['duration_s']:.3f}s path={r['solve_path']} "
                  f"reason={r.get('dump_reason')} "
                  f"degraded={r['degraded']}")
    print(f"-- backlog peak={verdict['backlog_peak']:.0f} "
          f"degraded={verdict['degraded']} "
          f"pending={verdict['pending']} bound={verdict['bound']}")
    print(f"VERDICT: {'GREEN' if verdict['green'] else 'RED'}")


#: training-record export schema (ISSUE 18 satellite).  Bump when a
#: field changes MEANING; adding optional fields is compatible.  One
#: JSONL line per flight round record:
#:
#:   schema_version  int    — this constant
#:   round           dict   — the RoundRecord doc verbatim (see
#:                            flight_recorder.RoundRecord: solve path,
#:                            phase timings, wall/device split, tenant,
#:                            cycle_seq + the critical-path join)
#:   timeline        dict?  — per-cycle observatory features for the
#:                            cycle the round ran in (null when the
#:                            recorder was off or the cycle aged out of
#:                            the ring): mode, wall_s, attribution
#:                            fractions, unattributed_fraction,
#:                            device_idle_fraction, critical_cause,
#:                            critical_seconds
#:   slo             dict   — the run's SLO burn snapshot keyed by SLO
#:                            name: breaches_total, peak_burn_fast,
#:                            peak_burn_slow (run-level, repeated per
#:                            line so each record is self-contained)
TRAINING_SCHEMA_VERSION = 1


def export_training_records(round_docs: list[dict],
                            cycle_docs: list[dict],
                            slo: dict, path: str) -> int:
    """Join flight records, timeline cycles, and the SLO snapshot into
    the versioned training JSONL (schema above).  Deterministic: same
    inputs yield byte-identical output (sorted keys, stable record
    order is the caller's contract).  Returns lines written."""
    by_cycle = {int(c["cycle"]): c for c in cycle_docs
                if c.get("cycle") is not None
                and c.get("mode") != "ingest"}
    slo_snapshot = {
        name: {"breaches_total": s.get("breaches_total", 0),
               "peak_burn_fast": (s.get("peak_burn") or {}).get("fast"),
               "peak_burn_slow": (s.get("peak_burn") or {}).get("slow")}
        for name, s in sorted((slo or {}).items())}
    n = 0
    with open(path, "w", encoding="utf-8") as fh:
        for rec in round_docs:
            cyc = by_cycle.get(rec.get("cycle_seq", -1))
            features = None
            if cyc is not None:
                features = {
                    "mode": cyc.get("mode"),
                    "wall_s": cyc.get("wall_s"),
                    "attribution": cyc.get("attribution"),
                    "unattributed_fraction":
                        cyc.get("unattributed_fraction"),
                    "device_idle_fraction":
                        cyc.get("device_idle_fraction"),
                    "critical_cause": cyc.get("critical_cause"),
                    "critical_seconds": cyc.get("critical_seconds"),
                }
            fh.write(json.dumps(
                {"schema_version": TRAINING_SCHEMA_VERSION,
                 "round": rec, "timeline": features,
                 "slo": slo_snapshot},
                sort_keys=True, default=str) + "\n")
            n += 1
    return n


def gather_training_inputs(harness) -> tuple[list[dict], list[dict]]:
    """Collect (round_docs, cycle_docs) from a finished harness in a
    deterministic order: tenants sorted by name (the untenanted
    scheduler as ""), each ring oldest-first; cycles newest-first from
    the observatory ring."""
    from koordinator_tpu import timeline

    front = getattr(harness, "front", None)
    if front is not None:
        schedulers = sorted(((t.name, t.scheduler)
                             for t in front.tenants()),
                            key=lambda pair: pair[0])
    else:
        schedulers = [("", harness.scheduler)]
    round_docs = []
    for _, sched in schedulers:
        round_docs.extend(
            rec.to_doc() for rec in list(sched.flight_recorder.records))
    cycle_docs = timeline.RECORDER.cycles(limit=1 << 20)
    return round_docs, cycle_docs


def forecast_ab_report(args) -> int:
    """The reactive-vs-predictive A/B scorecard (SOAK_FORECAST=1 /
    --forecast): one seeded diurnal trace through both arms, GREEN only
    when the predictive arm is no worse on breaches AND evictions and
    the proactive path actually ran (a predictive soak that never
    pre-staged a migration proves nothing about rebalance)."""
    from koordinator_tpu.forecast.ab import ABConfig, run_ab

    cfg = ABConfig(seed=args.seed)
    if args.nodes is not None:
        import dataclasses

        cfg = dataclasses.replace(cfg, nodes=args.nodes)
    doc = run_ab(cfg)
    print(f"== forecast A/B: seed={doc['seed']} nodes={doc['nodes']} "
          f"ticks={doc['ticks']} period={doc['period_s']:.0f}s "
          f"(one trace, two arms)")
    print(f"-- forecast {'metric':<26} {'reactive':>10} {'predictive':>11}")
    r, p = doc["reactive"], doc["predictive"]
    for key in ("slo_breach_minutes", "reactive_evictions",
                "be_pod_ticks", "prestaged_migrations",
                "migrations_completed"):
        print(f"   {key:<34} {r[key]:>10} {p[key]:>11}")
    err = ", ".join(f"{k}={v}" for k, v in
                    p.get("forecast_error_fraction", {}).items()) or "-"
    print(f"   {'forecast_error_fraction':<34} {'-':>10} {err:>11}")
    print(f"   {'horizon_s':<34} {'-':>10} "
          f"{p.get('horizon_s', 0.0):>11}")
    if args.json:
        print(json.dumps(doc, indent=2, default=str))
    green = doc["predictive_no_worse"] and p["prestaged_migrations"] > 0
    print(f"VERDICT: {'GREEN' if green else 'RED'}"
          + ("" if doc["predictive_no_worse"] else
             " (predictive arm WORSE than reactive)")
          + ("" if p["prestaged_migrations"] > 0 else
             " (zero pre-staged migrations — rebalance never ran)"))
    return 0 if green else 1


def drills_report(args) -> int:
    """The adversarial-drill verdict table (SOAK_DRILLS=1 / --drills):
    every catalog scenario (koordinator_tpu/drills/scenarios.py) runs
    once at the report seed — leader failover, manager restart, rack
    flap storm, quota reorg, tenant sever, warm restart — and the
    per-scenario check + RTO table prints.  GREEN only when every
    scenario's full verdict passed; a RED scenario prints its check
    breakdown and the exact replay handle."""
    import tempfile

    from koordinator_tpu.drills import run_all

    # drills validate at 6x compression (tests/test_drills_e2e.py uses
    # the same); the loadgen --time-scale default is tuned for churn
    # soaks, not for lease/breaker timing, so it is not reused here
    scale = 6.0
    with tempfile.TemporaryDirectory(prefix="koord-drills-") as workdir:
        verdicts = run_all(args.seed, workdir, time_scale=scale)
    print(f"== drills: seed={args.seed} scenarios={len(verdicts)} "
          f"time_scale={scale:g}x")
    print(f"-- drill {'scenario':<21} {'verdict':>7} {'rto_s':>8} "
          f"{'degraded_s':>11}  failed checks")
    all_green = True
    for name, v in verdicts.items():
        all_green = all_green and v.green
        failed = ", ".join(c.name for c in v.failed()) or "-"
        rto = "-" if v.rto_s is None else f"{v.rto_s:.2f}"
        print(f"   {name:<27} {'GREEN' if v.green else 'RED':>7} "
              f"{rto:>8} {v.degraded_s:>11.2f}  {failed}")
    if args.json:
        print(json.dumps({k: v.to_doc() for k, v in verdicts.items()},
                         indent=2, default=str))
    print(f"VERDICT: {'GREEN' if all_green else 'RED'}")
    for name, v in verdicts.items():
        if not v.green:
            print(f"-- {name} RED — replay: python -c \"from "
                  f"koordinator_tpu.drills import run_drill; "
                  f"print(run_drill({name!r}, {args.seed}, "
                  f"'/tmp/drill').render())\"")
            print(v.render())
    return 0 if all_green else 1


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(prog="soak_report")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--duration", type=float, default=None,
                        help="virtual seconds of churn (default: the "
                             "smoke config's 120)")
    parser.add_argument("--nodes", type=int, default=None)
    parser.add_argument("--arrival-rate", type=float, default=None)
    parser.add_argument("--time-scale", type=float, default=12.0,
                        help="virtual:wall compression (1 = real time)")
    parser.add_argument("--tenants", type=int, default=1,
                        help="simulate N clusters on one TenantScheduler "
                             "mesh (one churn process + socket stack per "
                             "tenant; the verdict gains a per-tenant "
                             "section)")
    parser.add_argument("--trace", default="",
                        help="replay this JSONL trace instead of "
                             "generating one from the seed")
    parser.add_argument("--inject-leak", choices=("thread", "queue"),
                        default=None,
                        help="self-test: inject a deliberate leak; the "
                             "verdict MUST come back red (exit flips: 0 "
                             "iff the leak was caught)")
    parser.add_argument("--slo-latency", type=float, default=2.5,
                        help="latency SLO threshold for the run "
                             "(CPU smoke rounds pay jit compilation; "
                             "the paper's bar is 0.2)")
    parser.add_argument("--quality-mode", choices=("off", "lp", "auto"),
                        default="off",
                        help="solve-quality mode for the soaked "
                             "scheduler(s); with a mode other than off "
                             "the report FAILS unless at least one "
                             "round actually solved on the quality "
                             "path (quality_rounds_total > 0) — a "
                             "quality soak that never exercised the "
                             "quality engine proves nothing")
    parser.add_argument("--quality-slack-threshold", type=float,
                        default=0.3,
                        help="auto-mode escalation bar (see the "
                             "scheduler's --quality-slack-threshold)")
    parser.add_argument("--forecast", action="store_true",
                        help="run the reactive-vs-predictive A/B smoke "
                             "instead of the churn soak: both arms "
                             "replay ONE seeded diurnal trace "
                             "(forecast/ab.py), the per-arm scorecard "
                             "prints, and the exit is GREEN only if "
                             "the predictive arm is no worse on "
                             "SLO-breach minutes and reactive "
                             "evictions — and actually pre-staged "
                             "at least one migration")
    parser.add_argument("--drills", action="store_true",
                        help="run the adversarial failure-drill catalog "
                             "instead of the churn soak: every scenario "
                             "(leader failover, manager restart, rack "
                             "storm, quota reorg, tenant sever, warm "
                             "restart) runs once at --seed and the "
                             "per-scenario verdict + RTO table prints; "
                             "exit 0 iff every scenario is GREEN")
    parser.add_argument("--json", action="store_true",
                        help="dump the raw verdict document too")
    parser.add_argument("--export-training-records", metavar="OUT",
                        default="",
                        help="also write the run's per-round training "
                             "records (flight record + per-cycle "
                             "timeline/critical-path features + SLO "
                             "burn snapshot, one JSONL line each; "
                             "schema_version "
                             f"{TRAINING_SCHEMA_VERSION}) to OUT")
    args = parser.parse_args(argv)

    if args.forecast:
        return forecast_ab_report(args)
    if args.drills:
        return drills_report(args)

    cfg = loadgen.smoke_config(seed=args.seed, tenants=args.tenants)
    overrides = {}
    if args.duration is not None:
        overrides["duration_s"] = args.duration
    if args.nodes is not None:
        overrides["nodes"] = args.nodes
    if args.arrival_rate is not None:
        overrides["arrival_rate"] = args.arrival_rate
    if overrides:
        import dataclasses

        cfg = dataclasses.replace(cfg, **overrides)
    events = (loadgen.read_trace(args.trace) if args.trace
              else loadgen.generate_trace(cfg))
    print(f"== churn soak: seed={cfg.seed} nodes={cfg.nodes} "
          f"duration={cfg.duration_s:.0f}s (virtual) "
          f"x{args.time_scale:g} compression — "
          f"{json.dumps(loadgen.trace_stats(events))}")
    with tempfile.TemporaryDirectory(prefix="koord-soak-") as workdir:
        harness = loadgen.SteadyStateHarness(
            cfg, workdir, time_scale=args.time_scale,
            slo_latency_threshold_s=args.slo_latency,
            inject_thread_leak=(args.inject_leak == "thread"),
            inject_queue_leak=(args.inject_leak == "queue"),
            quality_mode=args.quality_mode,
            quality_slack_threshold=args.quality_slack_threshold)
        harness.start()
        try:
            verdict = harness.run(events)
            from koordinator_tpu.scheduler import services as _services

            attach_host_wait(verdict, _services.debug_timeline_body(
                harness.scheduler, {"cycles": 512}))
            attach_journey(verdict)
            print_report(verdict, harness)
            if args.json:
                print(json.dumps(verdict, indent=2, default=str))
            if args.export_training_records:
                rounds, cycles = gather_training_inputs(harness)
                n = export_training_records(
                    rounds, cycles, verdict.get("slo") or {},
                    args.export_training_records)
                print(f"-- training records: {n} written to "
                      f"{args.export_training_records} "
                      f"(schema v{TRAINING_SCHEMA_VERSION})")
        finally:
            harness.close()
    if args.quality_mode != "off":
        from koordinator_tpu import metrics as _m

        quality_rounds = sum(v for _, v in _m.quality_rounds.items())
        print(f"-- quality: mode={args.quality_mode} "
              f"rounds={quality_rounds:g}")
        if quality_rounds <= 0:
            print("ERROR: quality soak ran zero quality rounds "
                  "(quality_rounds_total == 0)", file=sys.stderr)
            return 1
    if args.inject_leak:
        if verdict["trend"]["leaking"]:
            print(f"injected {args.inject_leak} leak CAUGHT: "
                  f"{verdict['trend']['leaking']}")
            return 0
        print(f"ERROR: injected {args.inject_leak} leak NOT caught",
              file=sys.stderr)
        return 1
    return 0 if verdict["green"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
