"""bench_stages.py (the stage-split profiler) must keep working: run it
as a subprocess at a tiny shape with the explicit ``--smoke`` flag and
assert every stage emits a record, keyed so a CPU timing can never pass
for the device metric."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_stage_profiler_smoke():
    env = dict(os.environ, KOORD_STAGES_NODES="64", KOORD_STAGES_PODS="256",
               KOORD_STAGES_METHODS="approx,chunked")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench_stages.py"), "--smoke"],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-2000:]
    records = [json.loads(line) for line in proc.stdout.splitlines()]
    stages = {r["stage"] for r in records}
    assert stages == {"provenance", "rtt_floor", "score", "select_approx",
                      "select_chunked", "rounds",
                      "refresh_incremental_1pct",
                      "lp_pack_smoke", "topo_gang_rank",
                      "score_sharded", "rounds_sharded", "merge_topk",
                      "score_sharded_1d", "rounds_sharded_1d",
                      "score_sharded_2d", "rounds_sharded_2d",
                      "sharded_2d_footprint",
                      "explain_compact_1pct", "explain_full_batch",
                      "wire_codec_v1_vs_v2", "deltasync_apply_batched",
                      "bind_commit_batched",
                      "tenancy_serial", "tenancy_pipelined",
                      "tenancy_batched", "timeline_overhead",
                      "journey_ledger_overhead"}, stages
    by_stage = {r["stage"]: r for r in records}
    # every timed stage produced a positive per-iteration time
    for name in ("score", "select_approx", "select_chunked", "rounds",
                 "refresh_incremental_1pct", "lp_pack_smoke",
                 "topo_gang_rank", "score_sharded",
                 "rounds_sharded", "merge_topk",
                 "score_sharded_1d", "rounds_sharded_1d",
                 "score_sharded_2d", "rounds_sharded_2d",
                 "explain_compact_1pct",
                 "explain_full_batch", "wire_codec_v1_vs_v2",
                 "deltasync_apply_batched", "bind_commit_batched",
                 "tenancy_serial",
                 "tenancy_pipelined", "tenancy_batched"):
        assert by_stage[name]["smoke_ms_per_iter"] > 0, by_stage[name]
        # a smoke timing never rides the device metric's key, and every
        # record names the platform it ran on
        assert "ms_per_iter" not in by_stage[name]
        assert by_stage[name]["platform"] == "cpu"
    # the host-plane turbo stages (ISSUE 19) record the legacy path
    # beside the batched one so bench_diff guards both inputs of the
    # speedup ratio
    assert by_stage["wire_codec_v1_vs_v2"]["v1_ms"] > 0
    assert by_stage["wire_codec_v1_vs_v2"]["speedup_vs_v1"] > 0
    assert by_stage["deltasync_apply_batched"]["per_event_ms"] > 0
    assert by_stage["deltasync_apply_batched"]["speedup_vs_per_event"] > 0
    assert by_stage["bind_commit_batched"]["per_pod_ms"] > 0
    assert by_stage["bind_commit_batched"]["speedup_vs_per_pod"] > 0
    # the quality stage reports its cost relative to the greedy rounds
    # it replaces on escalated rounds
    assert by_stage["lp_pack_smoke"]["vs_rounds_x"] > 0
    # the multi-tenant stage reports the acceptance observables: the
    # aggregate-rate ratio vs the serial baseline and the device-idle
    # fraction before/after pipelining (ISSUE 11)
    assert by_stage["tenancy_serial"]["device_idle_fraction"] is not None
    assert by_stage["tenancy_pipelined"]["speedup_vs_serial"] is not None
    assert by_stage["tenancy_pipelined"]["device_idle_fraction"] is not None
    # the stage capture stamps code and device provenance
    assert "commit" in by_stage["provenance"]
    assert by_stage["provenance"]["platform"] == "cpu"
    assert by_stage["provenance"]["device_kind"]
    # ... and FULL 2-D mesh provenance (ISSUE 14): device count, per-axis
    # split, axis names and the PxN shape string, on the provenance line
    # and on every sharded stage record
    assert by_stage["provenance"]["n_devices"] >= 1
    assert by_stage["provenance"]["mesh_axes"]["nodes"] >= 1
    assert by_stage["provenance"]["mesh_axes"]["pods"] >= 1
    assert by_stage["provenance"]["mesh_axis_names"] == ["pods", "nodes"]
    assert "x" in by_stage["provenance"]["mesh_shape"]
    assert by_stage["score_sharded"]["n_devices"] >= 1
    assert by_stage["score_sharded"]["mesh_axes"]["nodes"] >= 1
    # the 2-D comparison stages (ISSUE 14 acceptance observables): the
    # pods-split mesh reports its throughput ratio vs the all-nodes
    # mesh, and the per-device candidate-tensor footprint scales
    # ~1/pods_axis (exactly 1/2 at pods_axis=2)
    assert by_stage["score_sharded_2d"]["mesh_axes"]["pods"] == 2
    assert by_stage["score_sharded_2d"]["speedup_vs_1d"] > 0
    assert by_stage["rounds_sharded_2d"]["speedup_vs_1d"] > 0
    fp = by_stage["sharded_2d_footprint"]
    assert fp["ratio"] <= 0.51, fp
    # the explain overhead stages price themselves against the solve
    assert "pct_of_solve" in by_stage["explain_compact_1pct"]
    assert "within_5pct" in by_stage["explain_compact_1pct"]
    # the rounds stage really assigned pods (256 pods, ample capacity)
    assert by_stage["rounds"]["assigned_per_iter"] > 0
    # the timeline self-overhead stage (ISSUE 18) reports the on/off
    # wall comparison the perf sentinel gates; the fraction can dip
    # negative on timing noise but must exist and the timed wall must
    # be real
    assert by_stage["timeline_overhead"]["smoke_ms_per_iter"] > 0
    assert by_stage["timeline_overhead"]["overhead_fraction"] is not None
    # the journey-ledger self-overhead stage (ISSUE 20) measures the
    # ledger's hot-path seconds directly (shim accounting), so unlike
    # the wall-differenced delta its fraction is a real upper bound
    assert by_stage["journey_ledger_overhead"]["smoke_ms_per_iter"] > 0
    assert by_stage["journey_ledger_overhead"]["ledger_ms_per_iter"] >= 0
    assert by_stage["journey_ledger_overhead"]["overhead_fraction"] is not None


def test_bench_recall_smoke():
    """bench_recall.py must keep producing a parseable record under its
    explicit ``--smoke`` flag: tiny shape, at-shape leg off.  On CPU
    approx_max_k lowers exactly, so only the float-key quantization can
    cost recall — the mean should stay high."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", KOORD_RECALL_NODES="128",
               KOORD_RECALL_PODS="256", KOORD_RECALL_SHAPE_PODS="0")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench_recall.py"), "--smoke"],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-2000:]
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    assert rec["platform"] == "cpu"
    # an empty stamp outside a git checkout, never a failure
    assert isinstance(rec["provenance"]["commit"], str)
    # no wall clock of a CPU run under an unprefixed name
    assert not [k for k in rec if "wall_s" in k
                and not k.startswith("smoke_")]
    assert rec["candidate_recall_mean_256p_128n"] >= 0.8
    assert rec["assigned_frac_exact_256p_128n"] >= 0.9
    assert rec["assigned_frac_approx_256p_128n"] >= 0.9
