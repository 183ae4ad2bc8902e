#!/usr/bin/env python
"""Perf regression sentinel: diff two bench_stages captures.

Compares a candidate bench_stages JSONL capture against a committed
baseline, stage by stage, and exits non-zero when any stage regressed —
the gate ``tools/soak.sh`` runs (``SOAK_BENCH_DIFF=1``) so every soak
self-compares against the repo's committed baseline capture instead of
trusting that "the numbers looked fine".

A stage REGRESSED when BOTH hold (the two-sided bar keeps noise on
microsecond stages from flapping the gate):

  cand_ms > base_ms * (1 + tolerance)      relative slowdown
  cand_ms - base_ms > min-delta-ms         absolute slowdown floor

Also fatal: a baseline stage missing from the candidate, or present but
errored (a stage that stopped compiling is a regression, not a skip).
Stages only the candidate has are reported as NEW and pass — growing
the capture must not require lock-step baseline updates.

Non-stage lines are skipped by name: ``provenance`` (git/mesh metadata,
no timing) and ``rtt_floor`` (the host round-trip floor is machine
state, not code speed).  Baseline stages with no timing (a stage the
capture's device count could not run) are skipped too — they never
measured anything to regress from.

A ``bench_stages.py --smoke`` capture (tiny shape, any backend) keys its
timings ``smoke_ms_per_iter`` so they cannot pass for the device metric;
smoke diffs against smoke and device against device, never across.

Usage:
  python tools/bench_diff.py BASELINE.jsonl CANDIDATE.jsonl \
      [--tolerance 0.25] [--min-delta-ms 0.05]

Exit codes: 0 ok, 1 regression(s), 2 unusable input.
"""

from __future__ import annotations

import argparse
import json
import sys

#: lines that are capture metadata, not timed stages
SKIP_STAGES = frozenset({"provenance", "rtt_floor"})
#: a --smoke capture's timing field; load_stages folds it into
#: ``ms_per_iter`` and marks the record ``smoke``
SMOKE_KEY = "smoke_ms_per_iter"


def load_stages(path: str) -> dict[str, dict]:
    """Parse a bench_stages JSONL capture into {stage: record}.

    Malformed lines are ignored (a timeout mid-capture truncates the
    last line by design); an empty result is the caller's error.
    """
    stages: dict[str, dict] = {}
    with open(path, encoding="utf-8") as fh:
        for line in fh:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if not isinstance(rec, dict):
                continue
            stage = rec.get("stage")
            if not isinstance(stage, str) or stage in SKIP_STAGES:
                continue
            if SMOKE_KEY in rec:
                rec["ms_per_iter"] = rec.pop(SMOKE_KEY)
                rec["smoke"] = True
            stages[stage] = rec
    return stages


def is_smoke(stages: dict[str, dict]) -> bool:
    return any(rec.get("smoke") for rec in stages.values())


def diff_stages(base: dict[str, dict], cand: dict[str, dict],
                tolerance: float,
                min_delta_ms: float) -> tuple[list[dict], list[dict]]:
    """Compare captures; returns (regressions, report_rows).

    Every baseline stage yields one report row with a verdict:
    ``ok`` / ``improved`` / ``regressed`` / ``missing`` / ``errored`` /
    ``skipped`` (baseline itself errored); candidate-only stages get
    ``new``.  Rows are sorted by stage name so the report (and any
    golden-file diff of it) is deterministic.
    """
    regressions: list[dict] = []
    rows: list[dict] = []
    for stage in sorted(base):
        brec = base[stage]
        row: dict = {"stage": stage}
        if "error" in brec or "ms_per_iter" not in brec:
            row["verdict"] = "skipped"
            rows.append(row)
            continue
        base_ms = float(brec["ms_per_iter"])
        row["base_ms"] = base_ms
        crec = cand.get(stage)
        if crec is None:
            row["verdict"] = "missing"
            regressions.append(row)
            rows.append(row)
            continue
        if "error" in crec or "ms_per_iter" not in crec:
            row["verdict"] = "errored"
            row["error"] = str(crec.get("error", "no ms_per_iter"))[:200]
            regressions.append(row)
            rows.append(row)
            continue
        cand_ms = float(crec["ms_per_iter"])
        row["cand_ms"] = cand_ms
        row["ratio"] = round(cand_ms / base_ms, 3) if base_ms > 0 else None
        slow = (cand_ms > base_ms * (1.0 + tolerance)
                and cand_ms - base_ms > min_delta_ms)
        if slow:
            row["verdict"] = "regressed"
            regressions.append(row)
        elif cand_ms < base_ms:
            row["verdict"] = "improved"
        else:
            row["verdict"] = "ok"
        rows.append(row)
    for stage in sorted(set(cand) - set(base)):
        crec = cand[stage]
        row = {"stage": stage, "verdict": "new"}
        if "ms_per_iter" in crec:
            row["cand_ms"] = float(crec["ms_per_iter"])
        rows.append(row)
    return regressions, rows


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(
        description="diff two bench_stages JSONL captures; exit 1 on "
                    "regression")
    parser.add_argument("baseline", help="committed baseline capture")
    parser.add_argument("candidate", help="fresh capture to judge")
    parser.add_argument(
        "--tolerance", type=float, default=0.25,
        help="relative slowdown allowed before a stage regresses "
             "(0.25 = 25%%; soak sets this generously because the "
             "committed baseline was captured on different hardware)")
    parser.add_argument(
        "--min-delta-ms", type=float, default=0.05,
        help="absolute slowdown floor: a stage must ALSO be this many "
             "ms/iter slower to regress (keeps sub-0.1ms stages from "
             "flapping on scheduler jitter)")
    args = parser.parse_args(argv)

    try:
        base = load_stages(args.baseline)
        cand = load_stages(args.candidate)
    except OSError as e:
        print(f"bench_diff: cannot read capture: {e}", file=sys.stderr)
        return 2
    if not base:
        print(f"bench_diff: no timed stages in baseline "
              f"{args.baseline}", file=sys.stderr)
        return 2
    if not cand:
        print(f"bench_diff: no timed stages in candidate "
              f"{args.candidate}", file=sys.stderr)
        return 2

    if is_smoke(base) != is_smoke(cand):
        print("bench_diff: one capture is a --smoke run and the other is "
              "not; they do not measure the same thing", file=sys.stderr)
        return 2

    regressions, rows = diff_stages(base, cand, args.tolerance,
                                    args.min_delta_ms)
    for row in rows:
        print(json.dumps(row, sort_keys=True))
    n = len(regressions)
    if n:
        names = ", ".join(r["stage"] for r in regressions)
        print(f"bench_diff: FAIL — {n} stage(s) regressed beyond "
              f"{args.tolerance:.0%} (+{args.min_delta_ms}ms): {names}",
              file=sys.stderr)
        return 1
    print(f"bench_diff: ok — {len(rows)} stage(s) within "
          f"{args.tolerance:.0%}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
