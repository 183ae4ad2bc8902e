"""Perf regression sentinel (ISSUE 18 satellite): tools/bench_diff.py.

The acceptance criterion is the NEGATIVE test: a candidate capture with
a planted 2x slowdown must flip the exit code to 1 — the soak gate
(``SOAK_BENCH_DIFF=1`` in tools/soak.sh) is only worth wiring if the
sentinel actually fires.  Around it, the comparison rules: the
two-sided regression bar (relative slowdown AND absolute floor),
missing/errored candidate stages fatal, baseline-errored stages
skipped, metadata lines ignored, candidate-only stages pass as new.
"""

import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))

import bench_diff  # noqa: E402


def _write(path, records):
    with open(path, "w") as f:
        for rec in records:
            f.write(json.dumps(rec) + "\n")
    return str(path)


BASE = [
    {"stage": "provenance", "commit": "abc123", "dirty": False},
    {"stage": "rtt_floor", "ms_per_iter": 9.9},
    {"stage": "score", "ms_per_iter": 2.0},
    {"stage": "rounds", "ms_per_iter": 10.0},
    {"stage": "tiny", "ms_per_iter": 0.02},
    {"stage": "broken", "error": "RuntimeError('no mesh')"},
]


class TestLoadStages:
    def test_skips_metadata_malformed_and_blank_lines(self, tmp_path):
        p = tmp_path / "cap.jsonl"
        with open(p, "w") as f:
            f.write(json.dumps(BASE[0]) + "\n")
            f.write("\n")
            f.write('{"stage": "score", "ms_per_iter": 2.0}\n')
            f.write("[1, 2, 3]\n")
            f.write('{"no_stage_key": true}\n')
            f.write('{"stage": "trunca')      # timeout-truncated tail
        stages = bench_diff.load_stages(str(p))
        assert set(stages) == {"score"}

    def test_rtt_floor_is_machine_state_not_code_speed(self, tmp_path):
        stages = bench_diff.load_stages(_write(tmp_path / "b.jsonl", BASE))
        assert "rtt_floor" not in stages
        assert "provenance" not in stages


class TestDiffRules:
    def test_identical_captures_pass(self, tmp_path):
        base = bench_diff.load_stages(_write(tmp_path / "b.jsonl", BASE))
        regressions, rows = bench_diff.diff_stages(base, dict(base),
                                                   0.25, 0.05)
        assert regressions == []
        verdicts = {r["stage"]: r["verdict"] for r in rows}
        assert verdicts == {"score": "ok", "rounds": "ok", "tiny": "ok",
                            "broken": "skipped"}

    def test_two_sided_bar_needs_both_relative_and_absolute(self):
        base = {"s": {"stage": "s", "ms_per_iter": 10.0}}
        # relative breach without the absolute floor: 10 -> 13 at 25%
        # tolerance breaches relative, passes a 5ms floor
        regs, _ = bench_diff.diff_stages(
            base, {"s": {"stage": "s", "ms_per_iter": 13.0}}, 0.25, 5.0)
        assert regs == []
        # absolute breach without the relative one: +6ms on 100ms base
        base100 = {"s": {"stage": "s", "ms_per_iter": 100.0}}
        regs, _ = bench_diff.diff_stages(
            base100, {"s": {"stage": "s", "ms_per_iter": 106.0}}, 0.25, 5.0)
        assert regs == []
        # both breached -> regression
        regs, rows = bench_diff.diff_stages(
            base, {"s": {"stage": "s", "ms_per_iter": 20.0}}, 0.25, 5.0)
        assert [r["stage"] for r in regs] == ["s"]
        assert rows[0]["verdict"] == "regressed"
        assert rows[0]["ratio"] == 2.0

    def test_min_delta_floor_suppresses_microsecond_flaps(self):
        # a 0.02ms stage doubling is 100% relative but 0.02ms absolute:
        # scheduler jitter, not a regression
        base = {"tiny": {"stage": "tiny", "ms_per_iter": 0.02}}
        regs, rows = bench_diff.diff_stages(
            base, {"tiny": {"stage": "tiny", "ms_per_iter": 0.04}},
            0.25, 0.05)
        assert regs == []
        assert rows[0]["verdict"] == "ok"

    def test_missing_candidate_stage_is_fatal(self):
        base = {"s": {"stage": "s", "ms_per_iter": 1.0}}
        regs, rows = bench_diff.diff_stages(base, {}, 0.25, 0.05)
        assert rows[0]["verdict"] == "missing"
        assert regs == rows

    def test_errored_candidate_stage_is_fatal(self):
        base = {"s": {"stage": "s", "ms_per_iter": 1.0}}
        cand = {"s": {"stage": "s", "error": "Exception('boom')"}}
        regs, rows = bench_diff.diff_stages(base, cand, 0.25, 0.05)
        assert rows[0]["verdict"] == "errored"
        assert len(regs) == 1

    def test_baseline_errored_stage_skipped_even_if_candidate_times(self):
        base = {"s": {"stage": "s", "error": "never compiled"}}
        cand = {"s": {"stage": "s", "ms_per_iter": 5.0}}
        regs, rows = bench_diff.diff_stages(base, cand, 0.25, 0.05)
        assert regs == []
        assert rows[0]["verdict"] == "skipped"

    def test_candidate_only_stage_is_new_and_passes(self):
        base = {"s": {"stage": "s", "ms_per_iter": 1.0}}
        cand = {"s": {"stage": "s", "ms_per_iter": 1.0},
                "grown": {"stage": "grown", "ms_per_iter": 99.0}}
        regs, rows = bench_diff.diff_stages(base, cand, 0.25, 0.05)
        assert regs == []
        assert {r["stage"]: r["verdict"] for r in rows} == {
            "s": "ok", "grown": "new"}

    def test_improvement_is_named(self):
        base = {"s": {"stage": "s", "ms_per_iter": 10.0}}
        cand = {"s": {"stage": "s", "ms_per_iter": 4.0}}
        _, rows = bench_diff.diff_stages(base, cand, 0.25, 0.05)
        assert rows[0]["verdict"] == "improved"

    def test_rows_sorted_for_deterministic_reports(self):
        base = {n: {"stage": n, "ms_per_iter": 1.0}
                for n in ("zeta", "alpha", "mid")}
        _, rows = bench_diff.diff_stages(base, dict(base), 0.25, 0.05)
        assert [r["stage"] for r in rows] == ["alpha", "mid", "zeta"]


class TestExitCodes:
    """main() through its argv surface — what tools/soak.sh calls."""

    def test_identical_captures_exit_0(self, tmp_path, capsys):
        b = _write(tmp_path / "b.jsonl", BASE)
        assert bench_diff.main([b, b]) == 0

    def test_planted_2x_slowdown_exits_1(self, tmp_path, capsys):
        """THE acceptance criterion: the sentinel gates a planted
        regression non-zero."""
        b = _write(tmp_path / "b.jsonl", BASE)
        slowed = [dict(rec) for rec in BASE]
        for rec in slowed:
            if rec["stage"] == "rounds":
                rec["ms_per_iter"] = rec["ms_per_iter"] * 2.0
        c = _write(tmp_path / "c.jsonl", slowed)
        assert bench_diff.main([b, c, "--tolerance", "0.25",
                                "--min-delta-ms", "0.05"]) == 1
        err = capsys.readouterr().err
        assert "rounds" in err and "FAIL" in err

    def test_generous_tolerance_forgives_the_same_capture(
            self, tmp_path, capsys):
        b = _write(tmp_path / "b.jsonl", BASE)
        slowed = [dict(rec) for rec in BASE]
        for rec in slowed:
            if rec["stage"] == "rounds":
                rec["ms_per_iter"] = rec["ms_per_iter"] * 1.5
        c = _write(tmp_path / "c.jsonl", slowed)
        assert bench_diff.main([b, c, "--tolerance", "1.0"]) == 0

    def test_empty_or_unreadable_inputs_exit_2(self, tmp_path, capsys):
        b = _write(tmp_path / "b.jsonl", BASE)
        empty = _write(tmp_path / "empty.jsonl",
                       [{"stage": "provenance"}])
        assert bench_diff.main([empty, b]) == 2
        assert bench_diff.main([b, empty]) == 2
        assert bench_diff.main([str(tmp_path / "absent.jsonl"), b]) == 2

    def test_smoke_capture_never_diffs_against_a_device_capture(
            self, tmp_path, capsys):
        """--smoke timings ride their own key; they fold into the same
        comparison among themselves and are refused across kinds."""
        smoke = [{("smoke_ms_per_iter" if k == "ms_per_iter" else k): v
                  for k, v in rec.items()} for rec in BASE]
        s = _write(tmp_path / "s.jsonl", smoke)
        b = _write(tmp_path / "b.jsonl", BASE)
        assert bench_diff.load_stages(s)["score"]["ms_per_iter"] == 2.0
        assert bench_diff.main([s, s]) == 0
        assert bench_diff.main([b, s]) == 2
        assert bench_diff.main([s, b]) == 2

    def test_report_rows_are_json_lines(self, tmp_path, capsys):
        b = _write(tmp_path / "b.jsonl", BASE)
        assert bench_diff.main([b, b]) == 0
        out = capsys.readouterr().out
        rows = [json.loads(line) for line in out.splitlines() if line]
        assert {r["stage"] for r in rows} == {"score", "rounds", "tiny",
                                              "broken"}

    def test_cli_entrypoint_runs_standalone(self, tmp_path):
        """The soak gate shells out to the script — prove the file is
        executable as a program, not only importable."""
        b = _write(tmp_path / "b.jsonl", BASE)
        proc = subprocess.run(
            [sys.executable,
             os.path.join(os.path.dirname(__file__), "..", "tools",
                          "bench_diff.py"), b, b],
            capture_output=True, text=True, timeout=60)
        assert proc.returncode == 0, proc.stderr
        assert "ok" in proc.stderr


class TestCommittedBaseline:
    """The repo's committed smoke baseline must stay usable — the soak
    gate diffs fresh captures against it."""

    BASELINE = os.path.join(os.path.dirname(__file__), "..", "tools",
                            "baselines", "bench_stages_smoke.jsonl")

    def test_baseline_exists_and_parses(self):
        stages = bench_diff.load_stages(self.BASELINE)
        assert stages, "committed baseline has no timed stages"
        for name, rec in stages.items():
            if "skipped" not in rec:
                assert rec["ms_per_iter"] > 0, name
        assert bench_diff.is_smoke(stages)

    def test_baseline_self_diff_passes(self):
        assert bench_diff.main([self.BASELINE, self.BASELINE]) == 0

    def test_baseline_covers_the_host_turbo_stages(self):
        """The ISSUE 19 host-plane stages are part of the gated set."""
        stages = bench_diff.load_stages(self.BASELINE)
        for name in ("wire_codec_v1_vs_v2", "deltasync_apply_batched",
                     "bind_commit_batched"):
            rec = stages.get(name)
            assert rec is not None and "error" not in rec, name
            assert rec["ms_per_iter"] > 0, rec

    def test_planted_codec_regression_flagged(self, tmp_path, capsys):
        """THE ISSUE 19 acceptance: a candidate where the wire codec
        stage got 10x slower against the COMMITTED baseline must exit
        1 naming the stage — the sentinel really guards the codec."""
        slowed = []
        with open(self.BASELINE) as fh:
            for line in fh:
                rec = json.loads(line)
                if rec.get("stage") == "wire_codec_v1_vs_v2":
                    rec["smoke_ms_per_iter"] = round(
                        rec["smoke_ms_per_iter"] * 10 + 1.0, 2)
                slowed.append(rec)
        c = _write(tmp_path / "cand.jsonl", slowed)
        assert bench_diff.main([self.BASELINE, c]) == 1
        err = capsys.readouterr().err
        assert "wire_codec_v1_vs_v2" in err and "FAIL" in err

    def test_baseline_covers_the_timeline_overhead_stage(self):
        """The ISSUE's self-overhead stage must be part of the gated
        set, with its measured fraction under the 3% bar."""
        stages = bench_diff.load_stages(self.BASELINE)
        rec = stages.get("timeline_overhead")
        assert rec is not None and "error" not in rec
        assert rec["overhead_fraction"] < 0.03

    def test_baseline_covers_the_journey_ledger_overhead_stage(self):
        """ISSUE 20 acceptance: the always-on pod-journey ledger costs
        under 1% of the pipelined cycle (its scheduling-path work is
        stamps + staged appends; sketch digestion amortizes onto the
        telemetry sampler)."""
        stages = bench_diff.load_stages(self.BASELINE)
        rec = stages.get("journey_ledger_overhead")
        assert rec is not None and "error" not in rec, rec
        assert rec["ms_per_iter"] > 0
        assert rec["overhead_fraction"] < 0.01, rec

    def test_planted_journey_regression_flagged(self, tmp_path, capsys):
        """A candidate where the journey-ledger stage got 10x slower
        against the COMMITTED baseline must exit 1 naming the stage —
        the sentinel really guards the ledger's hot path."""
        slowed = []
        with open(self.BASELINE) as fh:
            for line in fh:
                rec = json.loads(line)
                if rec.get("stage") == "journey_ledger_overhead":
                    rec["smoke_ms_per_iter"] = round(
                        rec["smoke_ms_per_iter"] * 10 + 1.0, 2)
                slowed.append(rec)
        c = _write(tmp_path / "cand.jsonl", slowed)
        assert bench_diff.main([self.BASELINE, c]) == 1
        err = capsys.readouterr().err
        assert "journey_ledger_overhead" in err and "FAIL" in err
