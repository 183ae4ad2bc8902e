"""koordlint self-tests: the analyzer corpus contract + the whole-tree
gate (ISSUE 7).

Pure AST — this file never imports jax (which is also the marker-audit
rule it helps enforce).  Three layers:

- **corpus**: every rule flags its seeded known-bad fixture (including
  the reconstruction of the PR-1 ``ClusterState.zeros``
  donation-aliasing bug) and stays silent on the known-good twin;
- **tree**: ``python -m tools.koordlint`` semantics over THIS repo —
  zero unsuppressed findings, every suppression carries a reason, no
  stale baseline entries;
- **machinery**: inline ignores need reasons, reasonless baseline
  entries are findings, CLI exit codes.
"""

import json
import os
import subprocess
import sys
import tempfile

import pytest

from tools import koordlint
from tools.koordlint.analyzers.donation_flow import DonationFlowAnalyzer
from tools.koordlint.analyzers.donation_safety import DonationSafetyAnalyzer
from tools.koordlint.analyzers.dtype_regime import DtypeRegimeAnalyzer
from tools.koordlint.analyzers.jit_host_sync import JitHostSyncAnalyzer
from tools.koordlint.analyzers.latency_home import LatencyHomeAnalyzer
from tools.koordlint.analyzers.lock_discipline import LockDisciplineAnalyzer
from tools.koordlint.analyzers.marker_audit import MarkerAuditAnalyzer
from tools.koordlint.analyzers.mesh_discipline import MeshDisciplineAnalyzer
from tools.koordlint.analyzers.spec_consistency import (
    SpecConsistencyAnalyzer,
)
from tools.koordlint.analyzers.surface_parity import SurfaceParityAnalyzer
from tools.koordlint.analyzers.tenant_axis import TenantAxisAnalyzer
from tools.koordlint.analyzers.wire_codec import WireCodecAnalyzer
from tools.koordlint.analyzers import dashboard_drift
from tools.koordlint.core import (
    Project,
    SourceFile,
    apply_suppressions,
    load_baseline,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tools", "koordlint", "fixtures")


def corpus(rule: str, kind: str, targets) -> Project:
    return Project(os.path.join(FIXTURES, rule, kind), targets=targets)


class TestJitHostSyncCorpus:
    def analyzer(self):
        return JitHostSyncAnalyzer(package="pkg",
                                   root_paths=["pkg/solver.py"])

    def test_bad_corpus_flags_every_seeded_sync(self):
        findings = self.analyzer().run(
            corpus("jit_host_sync", "bad", ("pkg",)))
        messages = "\n".join(f.message for f in findings)
        for needle in ("host cast float()", "host cast int()",
                       "host cast bool()", "numpy.asarray()",
                       ".item() on a traced value",
                       "data-dependent branch",
                       "host iteration over a traced value"):
            assert needle in messages, f"missing: {needle}\n{messages}"
        # the interprocedural edge: the helper's branch is flagged too
        assert any("_helper" in f.message for f in findings)

    def test_good_corpus_is_clean(self):
        assert self.analyzer().run(
            corpus("jit_host_sync", "good", ("pkg",))) == []


class TestDonationSafetyCorpus:
    def test_bad_corpus_flags_the_pr1_bug_class(self):
        findings = DonationSafetyAnalyzer(package="pkg").run(
            corpus("donation_safety", "bad", ("pkg",)))
        messages = "\n".join(f.message for f in findings)
        # the PR-1 ClusterState.zeros reconstruction: one buffer,
        # several pytree fields
        assert "aliased across pytree fields" in messages
        assert "ClusterState.zeros" in messages   # names the bug class
        assert "read after being donated" in messages
        assert "also passed at position" in messages
        # the ISSUE-11 double-buffer anti-idiom: stashing the donated
        # in-flight buffer on a handle after dispatch is a second
        # read-after-donate seed (Pipeline.dispatch in the corpus); the
        # ISSUE-17 checkpoint path seeds a third (serialising the
        # pre-donation reference in Restorer.catch_up) plus a second
        # aliased construction (RestoredState.restore)
        assert messages.count("read after being donated") == 3
        assert messages.count("aliased across pytree fields") == 2
        assert len(findings) == 6

    def test_good_corpus_is_clean(self):
        assert DonationSafetyAnalyzer(package="pkg").run(
            corpus("donation_safety", "good", ("pkg",))) == []


class TestLockDisciplineCorpus:
    def test_bad_corpus_flags_cycle_and_bare_write(self):
        findings = LockDisciplineAnalyzer(package="pkg").run(
            corpus("lock_discipline", "bad", ("pkg",)))
        messages = "\n".join(f.message for f in findings)
        assert "lock-order cycle" in messages
        assert "Informer._lock" in messages and "Store._lock" in messages
        assert "race candidate" in messages
        assert "bare in reset()" in messages
        # multi-item `with a, b:` vs nested `with b: with a:` is a
        # cycle too (the combined form acquires in sequence)
        assert any("Combined._a" in f.message and "Combined._b"
                   in f.message for f in findings), messages
        # the ISSUE-17 checkpoint seeds: writer-lock / round-lock order
        # cycle, and the restore path's bare replay-cursor write
        assert any("RoundScheduler.lock" in f.message
                   and "CheckpointWriter._lock" in f.message
                   for f in findings), messages
        assert "bare in restore()" in messages

    def test_good_corpus_is_clean(self):
        # guarded-by annotation honored, RLock reentrancy not a cycle,
        # one-directional nesting not a cycle
        assert LockDisciplineAnalyzer(package="pkg").run(
            corpus("lock_discipline", "good", ("pkg",))) == []


class TestMeshDisciplineCorpus:
    def analyzer(self):
        return MeshDisciplineAnalyzer(package="pkg",
                                      capacity_home=("pkg/ops.py",))

    def test_bad_corpus_flags_every_seeded_violation(self):
        findings = self.analyzer().run(
            corpus("mesh_discipline", "bad", ("pkg",)))
        messages = "\n".join(f.message for f in findings)
        assert "omits in_specs and out_specs" in messages
        # donated-position gaps: missing entry, explicit None, and the
        # ISSUE-11 pipelined hand-off whose donated stacked state is
        # left to inference
        assert messages.count("has no explicit in_spec") == 3
        assert "raw check_node_capacity call outside" in messages
        assert len(findings) == 5

    def test_good_corpus_is_clean(self):
        # explicit specs everywhere, donated positions covered, the
        # capacity guard only inside its owning module
        assert self.analyzer().run(
            corpus("mesh_discipline", "good", ("pkg",))) == []


class TestSurfaceParityCorpus:
    def analyzer(self):
        return SurfaceParityAnalyzer(services_path="services.py",
                                     gateway_path="gateway.py")

    def test_bad_corpus_flags_drift_and_typed_error_gap(self):
        findings = self.analyzer().run(
            corpus("surface_parity", "bad",
                   ("services.py", "gateway.py")))
        messages = "\n".join(f.message for f in findings)
        assert "no matching dispatch" in messages        # route drift
        assert "never registers it" in messages          # reverse drift
        assert "without calling the shared builder" in messages
        assert "does not map it" in messages             # DebugApiError

    def test_good_corpus_is_clean(self):
        assert self.analyzer().run(
            corpus("surface_parity", "good",
                   ("services.py", "gateway.py"))) == []


class TestDashboardDriftCorpus:
    KNOWN = {"koord_registered_fixture_total",
             "koord_registered_fixture_seconds_bucket"}

    def test_bad_dashboard_flags_unregistered_metric(self):
        errors, checked = dashboard_drift.check_file(
            os.path.join(FIXTURES, "dashboard_drift", "bad_dash.json"),
            self.KNOWN)
        assert checked == 2
        assert len(errors) == 1
        assert "koord_metric_that_does_not_exist_total" in errors[0]

    def test_good_dashboard_is_clean(self):
        errors, checked = dashboard_drift.check_file(
            os.path.join(FIXTURES, "dashboard_drift", "good_dash.json"),
            self.KNOWN)
        assert (errors, checked) == ([], 2)


class TestMarkerAuditCorpus:
    def test_bad_corpus_flags_marker_and_import(self):
        findings = MarkerAuditAnalyzer().run(
            corpus("marker_audit", "bad", ("tests",)))
        messages = "\n".join(f.message for f in findings)
        assert "marked chaos but not slow" in messages
        assert "module-scope jax import" in messages
        assert len(findings) == 2   # the properly-marked test is silent

    def test_good_corpus_is_clean(self):
        assert MarkerAuditAnalyzer().run(
            corpus("marker_audit", "good", ("tests",))) == []


class TestDtypeRegimeCorpus:
    def analyzer(self):
        return DtypeRegimeAnalyzer(package="pkg", targets=("pkg/ops.py",))

    def test_bad_corpus_flags_the_packed_regime_wall(self):
        findings = self.analyzer().run(
            corpus("dtype_regime", "bad", ("pkg",)))
        messages = "\n".join(f.message for f in findings)
        # the reconstructed 2**15 ranking-key overflow: a 2**20-wide
        # clip pushes `q << 15` past int32
        assert "packed ranking-key arithmetic overflows" in messages
        # the unguarded packed composition: no _packed_regime gate, so
        # the tie-break field has no provable 15-bit bound
        assert "no provable bound" in messages
        assert "2**15" in messages
        # unseeded shift operand + the lying retN contract
        assert "cannot be proven to fit int32" in messages
        assert "shape annotation declares" in messages
        assert len(findings) == 5

    def test_good_corpus_is_clean(self):
        # guard + clip + rotation idiom + annotation seeds all prove
        assert self.analyzer().run(
            corpus("dtype_regime", "good", ("pkg",))) == []


class TestForecastCorpus:
    """The forecast kernels' seeded corpus (ISSUE 15): jit-host-sync on
    the horizon scalar, mesh-discipline on the sharded percentile —
    the two regressions forecast/kernels.py must never grow."""

    def sync_analyzer(self):
        return JitHostSyncAnalyzer(package="pkg",
                                   root_paths=["pkg/kernels.py"])

    def mesh_analyzer(self):
        return MeshDisciplineAnalyzer(package="pkg")

    def test_bad_corpus_flags_horizon_host_syncs(self):
        findings = self.sync_analyzer().run(
            corpus("forecast", "bad", ("pkg",)))
        messages = "\n".join(f.message for f in findings)
        assert "host cast float()" in messages       # float(horizon)
        assert "host cast int()" in messages         # int(horizon // 60)
        assert "data-dependent branch" in messages   # if growth > 0
        assert len(findings) == 3

    def test_bad_corpus_flags_sharded_percentile_specs(self):
        findings = self.mesh_analyzer().run(
            corpus("forecast", "bad", ("pkg",)))
        messages = "\n".join(f.message for f in findings)
        assert "omits in_specs and out_specs" in messages
        assert "has no explicit in_spec" in messages  # donated bank
        assert len(findings) == 2

    def test_good_corpus_is_clean(self):
        project = corpus("forecast", "good", ("pkg",))
        assert self.sync_analyzer().run(project) == []
        assert self.mesh_analyzer().run(project) == []


class TestSpecConsistencyCorpus:
    def analyzer(self):
        return SpecConsistencyAnalyzer(package="pkg")

    def test_bad_corpus_flags_every_seeded_violation(self):
        findings = self.analyzer().run(
            corpus("spec_consistency", "bad", ("pkg",)))
        messages = "\n".join(f.message for f in findings)
        assert "names an axis not live" in messages       # psum("pods")
        assert "in_specs declares 3 entries" in messages  # arity drift
        assert "out_specs declares 2 entries" in messages
        assert "replicas" in messages and "diverge" in messages
        assert "propagated layout contradicts" in messages
        # the 2-D regression seed (ISSUE 14): pod batch re-gathered
        # inside the round loop
        assert "inside a device loop body" in messages
        assert len(findings) == 6

    def test_good_corpus_is_clean(self):
        # right axis, aligned arities, sharded-base scatter (with the
        # shape-annotation layout seed), matched chained layouts, and
        # the 2-D gather-once-above-the-loop twin
        assert self.analyzer().run(
            corpus("spec_consistency", "good", ("pkg",))) == []


class TestDonationFlowCorpus:
    def analyzer(self):
        return DonationFlowAnalyzer(package="pkg")

    def test_bad_corpus_flags_missing_swap_and_stash(self):
        findings = self.analyzer().run(
            corpus("donation_flow", "bad", ("pkg",)))
        messages = "\n".join(f.message for f in findings)
        # the interprocedural kill: dispatch_without_swap leaves the
        # state dead, round()'s commit() call reads it two hops later
        assert "left dead" in messages
        assert "commit" in messages
        # the stash-the-donated-buffer tenancy anti-idiom — seeded in
        # pipeline.py AND in the quality rounding loop's pre-re-solve
        # stash (quality_rounding.py, ISSUE 13)
        assert "stash" in messages
        # direct dead reads: the rebound-alias non-swap (pipeline.py),
        # the rounding loop's missing SECOND swap after the residual
        # re-solve, and the residual re-solve's donated ASSIGNMENT
        # buffer read back afterwards (quality_rounding.py)
        assert messages.count("read after its buffers were donated") == 3
        assert "self.last_assignments" in messages
        by_file = {f.path for f in findings}
        assert by_file == {"pkg/pipeline.py", "pkg/quality_rounding.py"}
        assert len(findings) == 6

    def test_good_corpus_is_clean(self):
        # blessed swap, metadata reads, swap-through-method (the
        # adopt_state idiom), the rebind idiom, and the quality
        # rounding loop's swap-between-passes / merge-before-donating
        # twins all pass
        assert self.analyzer().run(
            corpus("donation_flow", "good", ("pkg",))) == []


class TestTenantAxisCorpus:
    def analyzer(self):
        return TenantAxisAnalyzer(package="pkg",
                                  targets=("pkg/front.py",))

    def test_bad_corpus_flags_unreduced_tenant_axis(self):
        findings = self.analyzer().run(
            corpus("tenant_axis", "bad", ("pkg",)))
        messages = "\n".join(f.message for f in findings)
        assert "still carries the leading tenant axis" in messages
        # the kit-entry contract from the shape annotation: on a jit
        # binding (argN) and on a kit method's named parameter
        assert messages.count("per-tenant contract") == 2
        assert len(findings) == 6

    def test_good_corpus_is_clean(self):
        # every slice _unstack'd (or [i]-indexed) before the sink
        assert self.analyzer().run(
            corpus("tenant_axis", "good", ("pkg",))) == []


class TestWireCodecCorpus:
    """ISSUE 19: per-event json.dumps on a frame type that has a v2
    columnar encoding is a finding — the rule that keeps the codec
    tentpole from quietly regressing to per-event JSON."""

    def analyzer(self):
        return WireCodecAnalyzer(package="pkg",
                                 codec_home=("pkg/wire.py",))

    def test_bad_corpus_flags_each_columnar_frame(self):
        findings = self.analyzer().run(
            corpus("wire_codec", "bad", ("pkg",)))
        messages = "\n".join(f.message for f in findings)
        # one seeded regression per columnar frame type: the per-event
        # STATE_PUSH send loop, the DELTA payload built from a
        # comprehension of dumps, the while-loop SNAPSHOT chunker
        for frame in ("STATE_PUSH", "DELTA", "SNAPSHOT"):
            assert f"FrameType.{frame}" in messages, messages
        assert len(findings) == 3
        assert all("events_v2" in f.message for f in findings)
        assert all("wire_protocol" in f.hint for f in findings)

    def test_good_corpus_is_clean(self):
        # per-frame dumps on columnar frames, a dumps loop with no
        # columnar frame in scope, and the exempted codec home's v1
        # fallback all pass
        assert self.analyzer().run(
            corpus("wire_codec", "good", ("pkg",))) == []

    def test_codec_home_exemption_is_load_bearing(self):
        # the same good corpus WITHOUT the exemption flags the v1
        # fallback packer — proof the default exemption for
        # transport/wire.py + deltasync.py is what keeps the real
        # tree's legacy path legal
        findings = WireCodecAnalyzer(package="pkg", codec_home=()).run(
            corpus("wire_codec", "good", ("pkg",)))
        assert [f.path for f in findings] == ["pkg/wire.py"]
        assert "pack_events_v1" in findings[0].message

    def test_real_transport_tree_is_clean(self, real_tree):
        # the shipped tree ships no per-event JSON on columnar frames
        # (the v1 paths live inside the exempt codec home; real_tree
        # reuses the shared whole-tree parse — the parse dominates)
        assert WireCodecAnalyzer().run(real_tree) == []


class TestLatencyHomeCorpus:
    def test_bad_corpus_flags_every_seeded_site(self):
        findings = LatencyHomeAnalyzer().run(
            corpus("latency_home", "bad", ("pkg",)))
        messages = "\n".join(f"{f.line}: {f.message}" for f in findings)
        assert len(findings) == 3, messages
        # one delta inside the bind loop, one against a stashed stamp
        # in the pending loop, one stored keyed by pod name
        for needle in ("inside `for (pod, node) in binds`",
                       "inside `for name in pending`",
                       "stored per pod under [pod.name]"):
            assert needle in messages, f"missing: {needle}\n{messages}"
        assert all("journey.LEDGER" in f.hint for f in findings)

    def test_good_corpus_round_scoped_deltas_stay_silent(self):
        assert LatencyHomeAnalyzer().run(
            corpus("latency_home", "good", ("pkg",))) == []

    def test_measurement_homes_are_exempt(self, real_tree):
        # journey.py itself subtracts clocks per pod BY DESIGN; the
        # rule must skip the sanctioned homes or it flags its own cure
        assert all(f.path not in ("koordinator_tpu/journey.py",
                                  "koordinator_tpu/timeline.py")
                   for f in LatencyHomeAnalyzer().run(real_tree))

    def test_real_tree_is_clean(self, real_tree):
        assert LatencyHomeAnalyzer().run(real_tree) == []


@pytest.fixture(scope="module")
def real_tree():
    """One whole-tree parse shared by every real-code specflow test
    (the parse dominates; SourceFiles are immutable so clones are
    cheap)."""
    return Project(REPO)


def clone_project(base: Project) -> Project:
    clone = object.__new__(Project)
    clone.root = base.root
    clone.files = dict(base.files)
    return clone


class TestSpecflowOnRealCode:
    """The acceptance demos: the proofs hold on the SHIPPED solver, and
    deliberately breaking a previously-unchecked invariant fails the
    build — not just on fixtures."""

    def _mutated(self, base, path, old, new):
        project = clone_project(base)
        src = project.files[path].text
        assert old in src, f"mutation anchor missing from {path}"
        fd, tmp = tempfile.mkstemp(suffix=".py")
        with os.fdopen(fd, "w") as f:
            f.write(src.replace(old, new, 1))
        project.files[path] = SourceFile(tmp, path)
        os.unlink(tmp)
        return project

    def test_real_batch_assign_proves_clean(self):
        # through the runner so the one reasoned inline ignore (the
        # trace-time float-scale shift) applies, as in the gate
        result = koordlint.run(REPO, rules=["dtype-regime"])
        assert result.findings == [], "\n".join(
            f.render() for f in result.findings)
        assert result.suppressed, "the reasoned inline ignore is live"

    def test_widened_clip_overflows_the_packed_key(self, real_tree):
        # the 2**15-wall class of bug, planted in the REAL solver: a
        # 2**20-wide score clip pushes `q << _TB_BITS` past int32
        project = self._mutated(
            real_tree,
            "koordinator_tpu/ops/batch_assign.py",
            "_SCORE_CLIP = (1 << 30 - _TB_BITS) - 1",
            "_SCORE_CLIP = (1 << 20) - 1")
        messages = "\n".join(
            f.message for f in DtypeRegimeAnalyzer().run(project))
        assert "packed ranking-key arithmetic overflows" in messages

    def test_removed_regime_guard_fails_the_field_proof(self, real_tree):
        # delete the packed/wide split: the tie-break field can reach
        # n_total - 1 > 2**15 and the rule must refuse the proof
        project = self._mutated(
            real_tree,
            "koordinator_tpu/ops/batch_assign.py",
            "key = ((q << _TB_BITS) | tb) if _packed_regime(n_total) "
            "else q",
            "key = (q << _TB_BITS) | tb")
        messages = "\n".join(
            f.message for f in DtypeRegimeAnalyzer().run(project))
        assert "reserves only 15 bits" in messages

    def test_real_scheduler_double_buffer_proves_clean(self, real_tree):
        findings = DonationFlowAnalyzer().run(clone_project(real_tree))
        assert findings == [], "\n".join(f.render() for f in findings)

    def test_dropped_unstack_in_real_tenancy_is_rank_drift(
            self, real_tree):
        # hand one tenant the still-stacked assignments instead of its
        # _unstack'd slice: the tenant-axis taint must reach the sink
        project = self._mutated(
            real_tree,
            "koordinator_tpu/scheduler/tenancy.py",
            "                self._unstack(a, i), "
            "self._unstack(st, i),",
            "                a, self._unstack(st, i),")
        messages = "\n".join(
            f.message for f in TenantAxisAnalyzer().run(project))
        assert "still carries the leading tenant axis" in messages

    def test_removed_blessed_swap_is_caught_interprocedurally(
            self, real_tree):
        # delete the dispatch half's re-point of snapshot.state: the
        # read surfaces FUNCTIONS AWAY (schedule_round's host-half
        # introspection) — the class donation-safety cannot see
        project = self._mutated(
            real_tree,
            "koordinator_tpu/scheduler/scheduler.py",
            "                self.snapshot.state = new_state\n",
            "")
        findings = DonationFlowAnalyzer().run(project)
        assert findings, "missing-swap mutation produced no findings"
        messages = "\n".join(f.message for f in findings)
        assert "self.snapshot.state" in messages
        assert "left dead" in messages


@pytest.fixture(scope="module")
def full_tree_run():
    """ONE full-suite CLI run shared by the whole-tree gate and the
    wall-clock guard (each whole-tree pass costs ~5s of tier-1)."""
    return subprocess.run(
        [sys.executable, "-m", "tools.koordlint", "--format", "json"],
        cwd=REPO, capture_output=True, text=True, timeout=120)


class TestWholeTree:
    """The gate tier-1 actually enforces: the shipped tree is clean."""

    def test_tree_is_clean_and_baseline_is_live(self, full_tree_run):
        assert full_tree_run.returncode == 0, (
            full_tree_run.stdout[-2000:] + full_tree_run.stderr)
        doc = json.loads(full_tree_run.stdout)
        assert doc["findings"] == []
        # the baseline is doing real work (grandfathered jax imports)
        # and every suppression carries a reason by construction
        assert doc["suppressed"]
        assert all(e["reason"].strip() for e in doc["suppressed"])
        # no dead weight: every baseline entry still matches something
        assert doc["stale_baseline"] == []

    def test_every_shipped_analyzer_has_a_corpus(self):
        for cls in koordlint.ALL_ANALYZERS:
            rule_dir = cls.name.replace("-", "_")
            assert os.path.isdir(os.path.join(FIXTURES, rule_dir)), (
                f"analyzer {cls.name} ships no fixture corpus")


class TestSuppressionMachinery:
    def _tmp_repo(self, tmp_path, body: str):
        tests = tmp_path / "tests"
        tests.mkdir()
        (tests / "test_seeded.py").write_text(body)
        return Project(str(tmp_path), targets=("tests",))

    def test_inline_ignore_with_reason_suppresses(self, tmp_path):
        project = self._tmp_repo(
            tmp_path,
            "import jax  "
            "# koordlint: ignore[marker-audit] -- perf fixture needs "
            "module-scope jax\n")
        findings = MarkerAuditAnalyzer().run(project)
        assert len(findings) == 1
        result = apply_suppressions(project, findings, [])
        assert result.findings == []
        assert len(result.suppressed) == 1
        assert "perf fixture" in result.suppressed[0][1]

    def test_inline_ignore_without_reason_is_a_finding(self, tmp_path):
        project = self._tmp_repo(
            tmp_path, "import jax  # koordlint: ignore[marker-audit]\n")
        findings = MarkerAuditAnalyzer().run(project)
        result = apply_suppressions(project, findings, [])
        rules = [f.rule for f in result.findings]
        assert "marker-audit" in rules       # NOT suppressed
        assert "lint-hygiene" in rules       # and the bad ignore flagged

    def test_baseline_entry_without_reason_is_a_finding(self, tmp_path):
        path = tmp_path / "baseline.json"
        path.write_text(json.dumps({"suppressions": [
            {"rule": "marker-audit", "path": "tests/test_x.py"}]}))
        entries, problems = load_baseline(str(path))
        assert entries == []
        assert len(problems) == 1
        assert problems[0].rule == "lint-hygiene"

    def test_shipped_baseline_reasons_are_mandatory_and_present(self):
        entries, problems = load_baseline(koordlint.BASELINE_PATH)
        assert problems == []
        assert entries
        assert all(e.reason.strip() for e in entries)


class TestCli:
    def _run(self, *args):
        return subprocess.run(
            [sys.executable, "-m", "tools.koordlint", *args],
            cwd=REPO, capture_output=True, text=True, timeout=120)

    def test_clean_tree_exits_zero(self):
        # one rule keeps the subprocess cheap; the FULL suite's
        # whole-tree gate runs in-process in TestWholeTree above
        proc = self._run("--rule", "marker-audit")
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "koordlint OK" in proc.stdout
        assert "suppressed-with-reason" in proc.stdout

    def test_new_finding_exits_nonzero(self, tmp_path):
        bad = tmp_path / "tests"
        bad.mkdir()
        (bad / "test_fresh.py").write_text("import jax\n")
        (tmp_path / "koordinator_tpu").mkdir()
        (tmp_path / "tools").mkdir()
        proc = self._run("--root", str(tmp_path))
        assert proc.returncode == 1
        assert "module-scope jax import" in proc.stdout

    def test_unknown_rule_exits_two(self):
        assert self._run("--rule", "no-such-rule").returncode == 2

    def test_list_rules_names_every_shipped_rule(self):
        proc = self._run("--list-rules")
        assert proc.returncode == 0
        for rule in ("jit-host-sync", "donation-safety", "lock-discipline",
                     "surface-parity", "dashboard-drift", "marker-audit",
                     "mesh-discipline", "spec-consistency", "dtype-regime",
                     "donation-flow", "tenant-axis"):
            assert rule in proc.stdout

    def test_format_json_is_machine_readable(self):
        proc = self._run("--rule", "marker-audit", "--format", "json")
        assert proc.returncode == 0, proc.stdout + proc.stderr
        doc = json.loads(proc.stdout)
        assert doc["findings"] == []
        assert doc["suppressed"], "baseline suppressions should appear"
        entry = doc["suppressed"][0]["finding"]
        # the pre-commit contract: file/line/rule/message/fix-hint
        assert set(entry) >= {"rule", "path", "line", "message", "hint"}
        assert doc["elapsed_s"] > 0

    def test_changed_only_filters_to_touched_files(self, tmp_path):
        repo = tmp_path / "repo"
        (repo / "tests").mkdir(parents=True)
        (repo / "koordinator_tpu").mkdir()
        (repo / "tools").mkdir()
        (repo / "tests" / "test_old.py").write_text("import jax\n")

        def git(*args):
            subprocess.run(["git", *args], cwd=repo, check=True,
                           capture_output=True, timeout=30)

        git("init", "-q")
        git("config", "user.email", "t@t")
        git("config", "user.name", "t")
        git("add", "-A")
        git("commit", "-qm", "seed")
        # a NEW bad file after the ref: only it may be reported
        (repo / "tests" / "test_new.py").write_text("import jax\n")
        proc = self._run("--root", str(repo), "--no-baseline",
                         "--changed-only", "HEAD", "--format", "json")
        doc = json.loads(proc.stdout)
        paths = {f["path"] for f in doc["findings"]}
        assert paths == {"tests/test_new.py"}, doc["findings"]
        assert proc.returncode == 1
        assert doc["changed_only"] == ["tests/test_new.py"]

    def test_full_tree_stays_inside_the_tier1_budget(self, full_tree_run):
        # the wall-clock guard the issue demands: the dataflow engine
        # must not silently eat the tier-1 budget.  elapsed_s is the
        # tool's own timing (interpreter startup excluded); the run is
        # shared with TestWholeTree's gate
        assert full_tree_run.returncode == 0, (
            full_tree_run.stdout[-2000:] + full_tree_run.stderr)
        doc = json.loads(full_tree_run.stdout)
        assert doc["elapsed_s"] < 20.0, (
            f"full-tree koordlint took {doc['elapsed_s']}s — the "
            "static-analysis suite is eating the tier-1 budget")


class TestRuntimeHelpers:
    def test_find_cycle(self):
        from tools.koordlint.runtime import find_cycle

        assert find_cycle({("a", "b"), ("b", "c")}) is None
        cycle = find_cycle({("a", "b"), ("b", "c"), ("c", "a")})
        assert cycle is not None and set(cycle) >= {"a", "b", "c"}

    def test_instrumented_lock_records_edges(self):
        import threading

        from tools.koordlint.runtime import (
            LockOrderRecorder,
            instrument_locks,
        )

        class Box:
            def __init__(self):
                self._outer = threading.Lock()
                self._inner = threading.Lock()

        box = Box()
        rec = LockOrderRecorder()
        # explicit cls_name overrides the module.Class default
        assert set(instrument_locks(box, rec, cls_name="Box")) == {
            "Box._outer", "Box._inner"}
        with box._outer:
            with box._inner:
                pass
        assert ("Box._outer", "Box._inner") in rec.edge_pairs()
        assert ("Box._inner", "Box._outer") not in rec.edge_pairs()
