#!/bin/bash
# Reproducible randomized soak over the property suites (a campaign run
# by hand is unreproducible).  Sweeps FRESH seed windows through every randomized
# invariant suite via the conftest prop_seeds knobs and prints one JSON
# tally line; CI keeps the cheap default seeds untouched.
#
# Usage:  tools/soak.sh            # 10 windows of the suites' default
#                                  # seed counts, bases 1000,2000,...
#         SOAK_WINDOWS=40 SOAK_COUNT=8 tools/soak.sh   # 40 windows x 8
#                                  # seeds per suite (~40*8*25 runs)
# Knobs:  SOAK_WINDOWS (default 10)  number of seed windows
#         SOAK_COUNT   (default 0)   seeds per suite per window
#                                    (0 = each suite's CI default count)
#         SOAK_BASE0   (default 1000) first window's seed base
#         SOAK_STRIDE  (default 1000) distance between window bases
#         SOAK_OUT     (default soak_results) output directory
#         SOAK_TRACE   (default 0)    1 = enable the JSONL trace
#                                     exporter (KOORD_TRACE_JSONL) for
#                                     every window and print the slowest
#                                     round's flight record at the end
#                                     (tools/trace_dump.py
#                                     --slowest-round)
#         SOAK_SLO     (default 1)    1 = end the run with the SLO
#                                     surface smoke: tools/slo_summary
#                                     drives a fresh scheduler+gateway
#                                     and prints per-SLO worst burn +
#                                     breach count from its live
#                                     /debug/slo (proves the SLO
#                                     machinery end to end; the pytest
#                                     windows run in their own
#                                     interpreters, so this is not a
#                                     readback of the soak itself)
#         SOAK_EXPLAIN (default 1)    1 = end the run with the
#                                     explainability smoke:
#                                     tools/explain_summary.py drives a
#                                     fresh scheduler+gateway with pods
#                                     failing for a known reason mix,
#                                     prints the top-unschedulable-
#                                     reasons tally from live
#                                     /debug/explain, and FAILS the
#                                     soak if any pod ends pending with
#                                     zero recorded reasons
#         SOAK_LOADGEN (default 0)    1 = end the run with the steady-
#                                     state smoke: tools/soak_report.py
#                                     replays a seeded churn trace
#                                     (loadgen) against a live
#                                     scheduler+manager+feeder over
#                                     real sockets, prints the
#                                     per-series trend verdict table
#                                     joined to flight records + SLO
#                                     breaches, and FAILS the soak on a
#                                     leak/drift (red) verdict; the
#                                     injected-thread-leak self-test
#                                     runs too (must come back red),
#                                     plus a 4-tenant multi-cluster
#                                     smoke whose per-tenant verdict
#                                     section must come back green
#         SOAK_QUALITY (default 0)    1 = end the run with the solve-
#                                     quality smoke: one loadgen soak
#                                     with --quality-mode auto (the
#                                     LP-relaxation packing engine
#                                     escalating on capacity slack);
#                                     the verdict must stay GREEN and
#                                     quality_rounds_total must be
#                                     nonzero — both enforced by
#                                     soak_report's exit status
#         SOAK_FORECAST (default 0)   1 = end the run with the
#                                     reactive-vs-predictive A/B smoke
#                                     (tools/soak_report.py --forecast):
#                                     both arms replay ONE seeded
#                                     diurnal trace (forecast/ab.py),
#                                     the per-arm scorecard prints
#                                     (SLO-breach minutes, reactive
#                                     evictions, pre-staged
#                                     migrations, forecast error), and
#                                     the soak FAILS unless the
#                                     predictive arm is no worse on
#                                     breaches and evictions and
#                                     pre-staged at least one
#                                     migration
#         SOAK_BENCH_DIFF (default 0) 1 = end the run with the perf
#                                     regression sentinel: a fresh
#                                     bench_stages --smoke capture is
#                                     diffed per-stage against the
#                                     committed baseline
#                                     (tools/baselines/
#                                     bench_stages_smoke.jsonl) via
#                                     tools/bench_diff.py and the soak
#                                     FAILS on any stage regressing
#                                     beyond SOAK_BENCH_DIFF_TOLERANCE
#                                     (default 1.0 = 100%: the
#                                     committed baseline was captured
#                                     on different hardware, so the
#                                     default only catches
#                                     order-of-magnitude rot; tighten
#                                     it when soaking on the baseline
#                                     machine)
#         SOAK_CHAOS   (default 0)    1 = also sweep the chaos
#                                     fault-injection suite (tests/
#                                     test_chaos.py, `chaos` marker)
#                                     across the same seed windows via
#                                     KOORD_CHAOS_SEED_BASE/_COUNT; a
#                                     failing window prints its seed
#                                     base so the exact fault schedule
#                                     replays with
#                                     KOORD_CHAOS_SEED_BASE=<base>
#         SOAK_DRILLS  (default 0)    1 = also sweep the adversarial
#                                     failure drills (tests/
#                                     test_drills_e2e.py, every catalog
#                                     scenario x the window's seeds) via
#                                     KOORD_DRILL_SEED_BASE/_COUNT; a
#                                     failing window prints its seed
#                                     base so the exact drill replays
#                                     with KOORD_DRILL_SEED_BASE=<base>,
#                                     and the run ends with the drill
#                                     verdict table (tools/
#                                     soak_report.py --drills: per-
#                                     scenario checks + measured RTO,
#                                     exit 0 iff all GREEN)
set -u
cd "$(dirname "$0")/.."

WINDOWS=${SOAK_WINDOWS:-10}
COUNT=${SOAK_COUNT:-0}
BASE0=${SOAK_BASE0:-1000}
STRIDE=${SOAK_STRIDE:-1000}
OUT=${SOAK_OUT:-soak_results}
CHAOS=${SOAK_CHAOS:-0}
DRILLS=${SOAK_DRILLS:-0}
LOADGEN=${SOAK_LOADGEN:-0}
QUALITY=${SOAK_QUALITY:-0}
FORECAST=${SOAK_FORECAST:-0}
TRACE=${SOAK_TRACE:-0}
SLO=${SOAK_SLO:-1}
EXPLAIN=${SOAK_EXPLAIN:-1}
BENCH_DIFF=${SOAK_BENCH_DIFF:-0}
BENCH_DIFF_TOLERANCE=${SOAK_BENCH_DIFF_TOLERANCE:-1.0}
BENCH_BASELINE=${SOAK_BENCH_BASELINE:-tools/baselines/bench_stages_smoke.jsonl}
mkdir -p "$OUT"
ts=$(date +%Y%m%d_%H%M%S)
log="$OUT/soak_$ts.log"

# static-analysis gate first: a soak over a tree with known invariant
# violations (jit host syncs, donation hazards, lock races, drifted
# debug surfaces) produces evidence nobody should trust.  Exits the
# soak's tally as a failure, never silently.
total_passed=0
total_failed=0
failures=""
echo "== koordlint static-analysis suite" \
    "(python -m tools.koordlint --format json)" | tee -a "$log"
if python -m tools.koordlint --format json >> "$log" 2>&1; then
    total_passed=$((total_passed + 1))
else
    total_failed=$((total_failed + 1))
    failures="$failures;koordlint: unsuppressed findings (see log -"
    failures="$failures run python -m tools.koordlint)"
fi

# dashboard drift gate (also a koordlint analyzer; the standalone shim
# stays for precise per-dashboard CLI output in the log)
echo "== dashboard drift check (tools/check_dashboards.py)" | tee -a "$log"
if python tools/check_dashboards.py >> "$log" 2>&1; then
    total_passed=$((total_passed + 1))
else
    total_failed=$((total_failed + 1))
    failures="$failures;dashboard drift: tools/check_dashboards.py failed"
    failures="$failures (see log)"
fi
trace_jsonl=""
if [ "$TRACE" = "1" ]; then
    trace_jsonl="$OUT/trace_$ts.jsonl"
    export KOORD_TRACE_JSONL="$trace_jsonl"
    echo "== tracing to $trace_jsonl" | tee -a "$log"
fi

SUITES="tests/test_deviceshare_properties.py \
tests/test_gang_properties.py \
tests/test_incremental_solve.py \
tests/test_lownodeload_properties.py \
tests/test_network_topology_properties.py \
tests/test_numa_properties.py \
tests/test_preemption_properties.py \
tests/test_quota_properties.py \
tests/test_replay_parity.py \
tests/test_reservation_properties.py \
tests/test_scheduler_accounting.py"

for ((w = 0; w < WINDOWS; w++)); do
    base=$((BASE0 + w * STRIDE))
    echo "== window $((w + 1))/$WINDOWS seed base $base" | tee -a "$log"
    KOORD_PROP_SEED_BASE=$base KOORD_PROP_SEED_COUNT=$COUNT \
        python -m pytest $SUITES -q --tb=line >> "$log" 2>&1
    rc=$?
    p=$(tail -40 "$log" | grep -oE "[0-9]+ passed" | tail -1 | grep -oE "[0-9]+")
    f=$(tail -40 "$log" | grep -oE "[0-9]+ failed" | tail -1 | grep -oE "[0-9]+")
    total_passed=$((total_passed + ${p:-0}))
    total_failed=$((total_failed + ${f:-0}))
    # a window that crashes without printing 'N failed' (collection
    # error, ImportError, OOM kill) must not count as green: trust
    # pytest's exit code over the summary grep.  Crash notes APPEND —
    # a later window's FAILED grep must not erase them.
    if [ "$rc" -ne 0 ] && [ "${f:-0}" -eq 0 ]; then
        total_failed=$((total_failed + 1))
        failures="$failures;window base=$base: pytest rc=$rc with no "
        failures="${failures}parsed failure count (crash — see log)"
    fi
    if [ "${f:-0}" -gt 0 ]; then
        failures="$failures;$(grep "^FAILED" "$log" | sort -u \
            | tr '\n' ';')"
    fi

    if [ "$CHAOS" = "1" ]; then
        echo "== chaos window $((w + 1))/$WINDOWS seed base $base" \
            | tee -a "$log"
        KOORD_CHAOS_SEED_BASE=$base KOORD_CHAOS_SEED_COUNT=$COUNT \
            python -m pytest tests/test_chaos.py -m chaos -q --tb=line \
            >> "$log" 2>&1
        crc=$?
        cp=$(tail -40 "$log" | grep -oE "[0-9]+ passed" | tail -1 \
            | grep -oE "[0-9]+")
        cf=$(tail -40 "$log" | grep -oE "[0-9]+ failed" | tail -1 \
            | grep -oE "[0-9]+")
        total_passed=$((total_passed + ${cp:-0}))
        if [ "$crc" -ne 0 ]; then
            total_failed=$((total_failed + ${cf:-1}))
            # the seed base IS the replay handle: rerun the exact fault
            # schedule with KOORD_CHAOS_SEED_BASE=<base>
            echo "CHAOS FAILURE at seed base $base — replay with" \
                "KOORD_CHAOS_SEED_BASE=$base python -m pytest" \
                "tests/test_chaos.py -m chaos" | tee -a "$log"
            failures="$failures;chaos seed base=$base rc=$crc:"
            failures="$failures $(grep '^FAILED' "$log" | sort -u \
                | tr '\n' ';')"
        fi
    fi

    if [ "$DRILLS" = "1" ]; then
        echo "== drill window $((w + 1))/$WINDOWS seed base $base" \
            | tee -a "$log"
        KOORD_DRILL_SEED_BASE=$base KOORD_DRILL_SEED_COUNT=$COUNT \
            python -m pytest tests/test_drills_e2e.py -m chaos -q \
            --tb=line >> "$log" 2>&1
        drc=$?
        dp=$(tail -40 "$log" | grep -oE "[0-9]+ passed" | tail -1 \
            | grep -oE "[0-9]+")
        df=$(tail -40 "$log" | grep -oE "[0-9]+ failed" | tail -1 \
            | grep -oE "[0-9]+")
        total_passed=$((total_passed + ${dp:-0}))
        if [ "$drc" -ne 0 ]; then
            total_failed=$((total_failed + ${df:-1}))
            # the seed base IS the replay handle: rerun the exact drill
            # (churn trace + storm schedule) with
            # KOORD_DRILL_SEED_BASE=<base>
            echo "DRILL FAILURE at seed base $base — replay with" \
                "KOORD_DRILL_SEED_BASE=$base python -m pytest" \
                "tests/test_drills_e2e.py -m chaos" | tee -a "$log"
            failures="$failures;drill seed base=$base rc=$drc:"
            failures="$failures $(grep '^FAILED' "$log" | sort -u \
                | tr '\n' ';')"
        fi
    fi
done

if [ "$DRILLS" = "1" ]; then
    # drill verdict table BEFORE the tally so its verdict counts in the
    # JSON: every catalog scenario runs once at the report seed and the
    # per-scenario check + RTO table prints; exit 0 iff all GREEN
    echo "== drill verdict table (soak_report --drills)" | tee -a "$log"
    if python tools/soak_report.py --drills >> "$log" 2>&1; then
        grep -E "^(== drills|-- |   |VERDICT)" "$log" | tail -12
        total_passed=$((total_passed + 1))
    else
        tail -16 "$log"
        total_failed=$((total_failed + 1))
        failures="$failures;drills: RED scenario verdict or harness"
        failures="$failures failure (see log)"
    fi
fi

if [ "$EXPLAIN" = "1" ]; then
    # explainability smoke BEFORE the tally so its verdict counts in the
    # JSON: top-unschedulable-reasons summary from a live
    # /debug/explain, failing if any pod ends pending with zero
    # recorded reasons (an unexplained pending pod = the reject-reason
    # accounting lost a pod)
    echo "== explainability smoke (tools/explain_summary.py)" | tee -a "$log"
    if python tools/explain_summary.py >> "$log" 2>&1; then
        tail -8 "$log"
        total_passed=$((total_passed + 1))
    else
        tail -8 "$log"
        total_failed=$((total_failed + 1))
        failures="$failures;explain smoke: pending pod with zero recorded"
        failures="$failures reasons or surface failure (see log)"
    fi
fi

if [ "$LOADGEN" = "1" ]; then
    # steady-state smoke BEFORE the tally so its verdict counts in the
    # JSON: a seeded churn soak must come back GREEN (no leak/drift, no
    # live SLO breach, bounded backlog), and the deliberate thread-leak
    # self-test must come back RED (a leak detector that can't catch a
    # planted leak proves nothing)
    echo "== steady-state smoke (tools/soak_report.py)" | tee -a "$log"
    if python tools/soak_report.py >> "$log" 2>&1; then
        grep -E "^(== steady|VERDICT|-- )" "$log" | tail -8
        total_passed=$((total_passed + 1))
    else
        tail -12 "$log"
        total_failed=$((total_failed + 1))
        failures="$failures;steady-state smoke: red verdict or harness"
        failures="$failures failure (see log)"
    fi
    echo "== injected-leak self-test (soak_report --inject-leak thread)" \
        | tee -a "$log"
    if python tools/soak_report.py --inject-leak thread >> "$log" 2>&1; then
        tail -2 "$log"
        total_passed=$((total_passed + 1))
    else
        tail -6 "$log"
        total_failed=$((total_failed + 1))
        failures="$failures;leak self-test: injected thread leak was NOT"
        failures="$failures caught (see log)"
    fi
    # multi-tenant smoke (ISSUE 11): four simulated clusters on one
    # TenantScheduler mesh — one churn process + socket stack + sync
    # binding per tenant; the verdict's per-tenant section must be
    # populated and GREEN (no tenant degraded)
    echo "== multi-tenant steady-state smoke (soak_report --tenants 4)" \
        | tee -a "$log"
    if python tools/soak_report.py --tenants 4 --duration 60 --nodes 16 \
            >> "$log" 2>&1; then
        grep -E "^(-- tenants|   t[0-9]|VERDICT)" "$log" | tail -7
        total_passed=$((total_passed + 1))
    else
        tail -12 "$log"
        total_failed=$((total_failed + 1))
        failures="$failures;multi-tenant smoke: red verdict or harness"
        failures="$failures failure (see log)"
    fi
fi

if [ "$QUALITY" = "1" ]; then
    # solve-quality smoke BEFORE the tally so its verdict counts in the
    # JSON: a churn soak with --quality-mode auto must come back GREEN
    # AND must have escalated at least one round onto the LP packing
    # path (soak_report exits nonzero on quality_rounds_total == 0)
    echo "== solve-quality smoke (soak_report --quality-mode auto)" \
        | tee -a "$log"
    if python tools/soak_report.py --quality-mode auto >> "$log" 2>&1; then
        grep -E "^(-- quality|VERDICT)" "$log" | tail -2
        total_passed=$((total_passed + 1))
    else
        tail -12 "$log"
        total_failed=$((total_failed + 1))
        failures="$failures;quality smoke: red verdict or zero quality"
        failures="$failures rounds (see log)"
    fi
fi

if [ "$FORECAST" = "1" ]; then
    # forecast A/B smoke BEFORE the tally so its verdict counts in the
    # JSON: the reactive and predictive arms replay one seeded diurnal
    # trace; the predictive arm must be no worse on SLO-breach minutes
    # and reactive evictions AND must have pre-staged at least one
    # reservation-first migration (both enforced by soak_report's exit)
    echo "== forecast A/B smoke (soak_report --forecast)" | tee -a "$log"
    if python tools/soak_report.py --forecast >> "$log" 2>&1; then
        grep -E "^(== forecast|-- forecast|   |VERDICT)" "$log" | tail -9
        total_passed=$((total_passed + 1))
    else
        tail -12 "$log"
        total_failed=$((total_failed + 1))
        failures="$failures;forecast A/B: predictive arm worse than"
        failures="$failures reactive or zero prestaged migrations (see log)"
    fi
fi

if [ "$BENCH_DIFF" = "1" ]; then
    # perf regression sentinel BEFORE the tally so its verdict counts
    # in the JSON: capture bench_stages --smoke fresh and diff every
    # stage against the committed baseline; any stage beyond the
    # tolerance (or missing/errored) fails the soak
    bench_capture="$OUT/bench_stages_$ts.jsonl"
    echo "== perf regression sentinel (bench_stages --smoke vs" \
        "$BENCH_BASELINE, tolerance $BENCH_DIFF_TOLERANCE)" | tee -a "$log"
    if python bench_stages.py --smoke > "$bench_capture" 2>> "$log" \
            && python tools/bench_diff.py "$BENCH_BASELINE" \
                "$bench_capture" --tolerance "$BENCH_DIFF_TOLERANCE" \
                >> "$log" 2>&1; then
        grep -E "bench_diff:" "$log" | tail -1
        total_passed=$((total_passed + 1))
    else
        grep -E "\"verdict\": \"(regressed|missing|errored)\"|bench_diff:" \
            "$log" | tail -6
        total_failed=$((total_failed + 1))
        failures="$failures;bench_diff: stage regression vs committed"
        failures="$failures baseline (see log and $bench_capture)"
    fi
fi

# the tally is built by python so failure text (quotes, backslashes in
# assert messages) can never produce invalid JSON
json="$OUT/soak_$ts.json"
SOAK_TALLY_FAILURES="$failures" python - "$WINDOWS" "$COUNT" "$BASE0" \
        "$STRIDE" "$total_passed" "$total_failed" "$log" <<'PYEOF' \
    | tee "$json"
import json
import os
import sys

w, c, b, s, p, f, log = sys.argv[1:8]
print(json.dumps({
    "windows": int(w),
    "seeds_per_suite_per_window": (int(c) or "suite-default"),
    "base0": int(b), "stride": int(s),
    "total_passed": int(p), "total_failed": int(f),
    "failures": os.environ.get("SOAK_TALLY_FAILURES", "").strip(";"),
    "log": log,
}))
PYEOF

if [ "$TRACE" = "1" ] && [ -s "$trace_jsonl" ]; then
    echo "== slowest round ($trace_jsonl)" | tee -a "$log"
    python tools/trace_dump.py "$trace_jsonl" --slowest-round \
        | tee -a "$log"
fi
if [ "$SLO" = "1" ]; then
    # SLO surface smoke from a live /debug/slo (fresh synthetic drive
    # over the gateway — not a readback of the pytest windows above):
    # per-SLO worst burn rate + breach count
    python tools/slo_summary.py | tee -a "$log" \
        || echo "WARNING: slo_summary failed (see log)" | tee -a "$log"
fi
[ "$total_failed" -eq 0 ]
