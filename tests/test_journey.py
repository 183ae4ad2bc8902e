"""Pod-journey ledger (ISSUE 20): sketch algebra, e2e recording flow,
fleet merge, wire threading of arrival_ts, debug surfaces, and — the
load-bearing guarantee — bit-identity of scheduling decisions and quota
charges with the ledger on vs off.

The sketch tests pin the DDSketch contract the fleet aggregation leans
on: merge is associative + commutative with the empty sketch as
identity AND byte-deterministic (``to_doc`` of equal sketches is equal
JSON), and every quantile stays within the declared <=1% relative
error across six decades of latencies at once — a fixed-bucket
histogram cannot do that, which is why the ledger exists.
"""

import json
import os
import time

import numpy as np
import pytest

from koordinator_tpu import journey
from koordinator_tpu.api.resources import resource_vector
from koordinator_tpu.journey import (
    DDSketch,
    JourneyLedger,
    RELATIVE_ACCURACY,
    merge_snapshot_rows,
)
from koordinator_tpu.scheduler.scheduler import Scheduler
from koordinator_tpu.scheduler.services import (
    DebugApiError,
    debug_latency_body,
)
from koordinator_tpu.scheduler.snapshot import ClusterSnapshot, PodSpec
from koordinator_tpu.transport.deltasync import (
    SchedulerBinding,
    StateSyncService,
)


def canon(sk: DDSketch) -> str:
    # "sum" is the one doc field whose low bits depend on float
    # accumulation ORDER, not on which samples were seen — byte
    # determinism is claimed (and asserted) for everything else.
    doc = sk.to_doc()
    doc.pop("sum", None)
    return json.dumps(doc, sort_keys=True)


def sketch_of(values) -> DDSketch:
    sk = DDSketch()
    sk.insert_many(values)
    return sk


@pytest.fixture(autouse=True)
def _fresh_ledger():
    journey.LEDGER.set_enabled(True)
    journey.LEDGER.reset_for_tests()
    yield
    journey.LEDGER.set_enabled(True)
    journey.LEDGER.reset_for_tests()


class TestSketchAlgebra:
    def test_merge_commutative(self):
        a = sketch_of([0.001, 0.5, 3.0, 0.02])
        b = sketch_of([1e-4, 7.0, 0.3])
        ab = a.copy().merge(b)
        ba = b.copy().merge(a)
        assert canon(ab) == canon(ba)

    def test_merge_associative(self):
        a = sketch_of([0.001, 0.5])
        b = sketch_of([0.02, 90.0])
        c = sketch_of([5e-4, 0.25, 1.5])
        left = a.copy().merge(b).merge(c)           # (a+b)+c
        bc = b.copy().merge(c)
        right = a.copy().merge(bc)                  # a+(b+c)
        assert canon(left) == canon(right)

    def test_empty_sketch_is_merge_identity(self):
        a = sketch_of([0.004, 0.2, 12.0])
        before = canon(a)
        assert canon(a.copy().merge(DDSketch())) == before
        assert canon(DDSketch().merge(a)) == before
        assert DDSketch().merge(DDSketch()).count == 0
        assert DDSketch().quantile(0.99) is None

    def test_merge_equals_sketch_of_concatenation(self):
        """Merge is LOSS-FREE: merging two sketches gives exactly the
        sketch of the concatenated samples (bucket-wise add)."""
        rng = np.random.RandomState(7)
        xs = rng.lognormal(-4, 2, 500)
        ys = rng.lognormal(-2, 1, 300)
        merged = sketch_of(xs).merge(sketch_of(ys))
        whole = sketch_of(np.concatenate([xs, ys]))
        assert canon(merged) == canon(whole)
        assert merged.to_doc()["sum"] == pytest.approx(
            whole.to_doc()["sum"])

    def test_relative_error_bound_across_six_decades(self):
        """Property test: quantiles stay within the declared relative
        accuracy from 100us to 100s — six decades in ONE sketch."""
        rng = np.random.RandomState(20)
        # uniform in log-space across [1e-4, 1e2)
        values = 10.0 ** rng.uniform(-4, 2, 20_000)
        sk = DDSketch()
        sk.insert_batch(values)
        hi = np.sort(values)
        for q in (0.01, 0.1, 0.5, 0.9, 0.99, 0.999):
            est = sk.quantile(q)
            true = float(hi[int(q * (len(hi) - 1))])
            rel = abs(est - true) / true
            assert rel <= RELATIVE_ACCURACY, (q, est, true, rel)

    def test_vectorized_insert_matches_scalar_inserts(self):
        rng = np.random.RandomState(3)
        values = rng.lognormal(-3, 2, 2_000)
        batched = DDSketch()
        batched.insert_batch(values)
        scalar = sketch_of(values)
        assert canon(batched) == canon(scalar)
        assert batched.to_doc()["sum"] == pytest.approx(
            scalar.to_doc()["sum"])

    def test_to_doc_roundtrip_is_byte_deterministic(self):
        sk = sketch_of([0.002, 0.4, 0.0, 25.0, 3e-4])
        doc = sk.to_doc()
        wire = json.dumps(doc, sort_keys=True)
        back = DDSketch.from_doc(json.loads(wire))
        assert json.dumps(back.to_doc(), sort_keys=True) == wire
        # bucket keys serialize in sorted order — equal sketches give
        # equal BYTES without a canonicalization pass
        assert list(doc["buckets"]) == sorted(doc["buckets"],
                                              key=lambda k: int(k))

    def test_zero_and_negative_values_land_in_zero_bucket(self):
        sk = sketch_of([0.0, -1.0, 5e-10])
        assert sk.zero_count == 3 and sk.count == 3
        assert sk.quantile(0.5) == 0.0


class TestLedger:
    def _pods(self, n, qos=0):
        return [PodSpec(name=f"p{i}", requests=np.zeros(4, np.int32),
                        qos=qos) for i in range(n)]

    def test_record_batch_populates_all_stages(self):
        led = JourneyLedger()
        pods = self._pods(4)
        arrived = time.time() - 0.005
        for p in pods:
            led.note_enqueue(p.name, arrival_ts=arrived)
        t = time.perf_counter()
        led.record_bind_batch("a", pods, round_start_perf=t,
                              commit_perf=t + 0.001, ack_perf=t + 0.002)
        stages = {r["stage"] for r in led.report()["series"]}
        assert stages == set(journey.STAGES)
        e2e = [r for r in led.report("a")["series"]
               if r["stage"] == "e2e"][0]
        assert e2e["count"] == 4 and e2e["p99_s"] > 0

    def test_no_arrival_stamp_skips_ingest_stage(self):
        led = JourneyLedger()
        pods = self._pods(2)
        for p in pods:
            led.note_enqueue(p.name)
        t = time.perf_counter()
        led.record_bind_batch("a", pods, round_start_perf=t,
                              commit_perf=t)
        stages = {r["stage"] for r in led.report()["series"]}
        assert "ingest" not in stages and "e2e" in stages

    def test_qos_classes_get_separate_series(self):
        led = JourneyLedger()
        pods = self._pods(2, qos=0) + [
            PodSpec(name="be", requests=np.zeros(4, np.int32), qos=3)]
        for p in pods:
            led.note_enqueue(p.name)
        t = time.perf_counter()
        led.record_bind_batch("a", pods, round_start_perf=t,
                              commit_perf=t)
        qos_seen = {(r["qos"], r["stage"])
                    for r in led.report()["series"]}
        assert (0, "e2e") in qos_seen and (3, "e2e") in qos_seen

    def test_forget_drops_stamps_and_unstamped_pods_are_skipped(self):
        led = JourneyLedger()
        led.note_enqueue("gone")
        led.forget("gone")
        t = time.perf_counter()
        led.record_bind_batch("a", self._pods(1),
                              round_start_perf=t, commit_perf=t)
        assert led.report()["series"] == []
        assert led.pending_count() == 0

    def test_disabled_ledger_records_nothing_and_clears(self):
        led = JourneyLedger()
        led.note_enqueue("p0")
        led.set_enabled(False)
        assert led.pending_count() == 0
        led.note_enqueue("p1")
        t = time.perf_counter()
        led.record_bind_batch("a", self._pods(2),
                              round_start_perf=t, commit_perf=t)
        assert led.report()["series"] == []

    def test_jsonl_snapshot_merges_to_fleet_table(self, tmp_path):
        """Two 'processes' flush JSONL; the merged table equals the
        single-process table over the union of their samples."""
        t = time.perf_counter()
        led1, led2 = JourneyLedger(), JourneyLedger()
        for led, names in ((led1, ("a0", "a1")), (led2, ("b0",))):
            pods = [PodSpec(name=n, requests=np.zeros(4, np.int32))
                    for n in names]
            for p in pods:
                led.note_enqueue(p.name)
            led.record_bind_batch("t0", pods, round_start_perf=t,
                                  commit_perf=t)
        p1, p2 = str(tmp_path / "a.jsonl"), str(tmp_path / "b.jsonl")
        assert led1.write_jsonl(p1) > 0
        assert led2.write_jsonl(p2) > 0
        rows = []
        for path in (p1, p2):
            with open(path) as fh:
                rows.extend(json.loads(line) for line in fh)
        merged = merge_snapshot_rows(rows)
        e2e = merged[("t0", 0, "e2e")]
        assert e2e.count == 3


class TestLatencyReport:
    def test_cli_merges_files_into_one_table(self, tmp_path, capsys):
        import sys
        sys.path.insert(0, os.path.join(
            os.path.dirname(__file__), "..", "tools"))
        import latency_report

        led = JourneyLedger()
        pods = [PodSpec(name=f"x{i}", requests=np.zeros(4, np.int32))
                for i in range(3)]
        for p in pods:
            led.note_enqueue(p.name)
        t = time.perf_counter()
        led.record_bind_batch("ten", pods, round_start_perf=t,
                              commit_perf=t + 0.001)
        path = str(tmp_path / "one.jsonl")
        led.write_jsonl(path)
        assert latency_report.main([path, path]) == 0   # self-merge: 2x
        out = capsys.readouterr().out
        assert "ten" in out and "e2e" in out
        table = latency_report.journey_table(
            latency_report.read_rows([path, path]))
        e2e = [r for r in table["series"] if r["stage"] == "e2e"][0]
        assert e2e["count"] == 6 and e2e["p99_s"] is not None

    def test_empty_inputs_exit_2(self, tmp_path, capsys):
        import sys
        sys.path.insert(0, os.path.join(
            os.path.dirname(__file__), "..", "tools"))
        import latency_report

        empty = tmp_path / "empty.jsonl"
        empty.write_text("\nnot json\n{\"unrelated\": 1}\n")
        assert latency_report.main([str(empty)]) == 2


def _assemble():
    snap = ClusterSnapshot(capacity=8)
    sched = Scheduler(snap)
    svc = StateSyncService()
    svc.attach_binding(SchedulerBinding(sched))
    svc.upsert_node("n1", np.asarray(
        resource_vector(cpu=64_000, memory=262_144), np.int32))
    return sched, svc


class TestWireThreading:
    def test_arrival_ts_survives_deltasync_into_podspec(self):
        sched, svc = _assemble()
        stamp = time.time() - 0.25
        svc.add_pod("p1", np.asarray(
            resource_vector(cpu=1_000, memory=1_024), np.int32),
            arrival_ts=stamp)
        assert sched.pending["p1"].arrival_ts == pytest.approx(stamp)

    def test_stampless_pod_add_defaults_to_zero(self):
        sched, svc = _assemble()
        svc.add_pod("p1", np.asarray(
            resource_vector(cpu=1_000, memory=1_024), np.int32))
        assert sched.pending["p1"].arrival_ts == 0.0
        # and no arrival_ts key pollutes the stored doc (sparse column:
        # absent means absent)
        assert "arrival_ts" not in svc.pods["p1"]["doc"]

    def test_non_numeric_arrival_ts_rejected_by_push_validation(self):
        from koordinator_tpu.transport.wire import WireSchemaError

        _sched, svc = _assemble()
        before_rv = svc.rv
        with pytest.raises(WireSchemaError, match="arrival_ts"):
            svc._handle_state_push(
                {"kind": "pod_add", "name": "bad", "priority": 0,
                 "arrival_ts": "yesterday"},
                {"requests": np.asarray(
                    resource_vector(cpu=1_000, memory=1_024), np.int32)})
        assert svc.rv == before_rv  # rejected push commits nothing

    def test_bound_pod_lands_in_ledger_via_real_round(self):
        sched, svc = _assemble()
        svc.add_pod("p1", np.asarray(
            resource_vector(cpu=1_000, memory=1_024), np.int32),
            arrival_ts=time.time() - 0.01)
        res = sched.schedule_round()
        assert res.assignments == {"p1": "n1"}
        series = journey.LEDGER.report()["series"]
        stages = {r["stage"] for r in series}
        assert {"e2e", "ingest", "queue_wait", "solve",
                "commit"} <= stages


class TestCutByTime:
    """ISSUE 34: the same sketches, cut to a range of commit stamps by
    merging the ring's per-round slices."""

    def _rounds(self, led, n_rounds, seed=0, mixed_qos=False,
                one_by_one=(), read_after=()):
        """``n_rounds`` bind rounds a second apart on a made-up clock;
        returns the per-series reference sketches, built sample by
        sample, for every prefix: ``ref[k]`` covers rounds < k."""
        rng = np.random.default_rng(seed)
        refs = [{}]
        base = 1_000.0
        for r in range(n_rounds):
            start = base + r
            commit_at = start + (0.010 + 0.001 * r)
            ack_at = commit_at + 0.002
            # as the ledger takes them: differences of stamps
            solve_s, commit_s = commit_at - start, ack_at - commit_at
            pods = [PodSpec(name=f"r{r}p{i}",
                            requests=np.zeros(4, np.int32),
                            qos=(i % 2) * 3 if mixed_qos else 0)
                    for i in range(int(rng.integers(3, 9)))]
            waits = rng.uniform(0.0, 0.5, len(pods))
            for pod, w in zip(pods, waits):
                led._pending[pod.name] = (0.0, 0.0, start - float(w))
            batches = ([[p] for p in pods] if r in one_by_one else [pods])
            for batch in batches:
                led.record_bind_batch(
                    "a", batch, round_start_perf=start,
                    commit_perf=commit_at, ack_perf=ack_at)
            if r in read_after:
                led.report()
            ref = {k: sk.copy() for k, sk in refs[-1].items()}
            for pod, w in zip(pods, waits):
                for stage, v in (("queue_wait", start - (start - float(w))),
                                 ("solve", solve_s), ("commit", commit_s)):
                    ref.setdefault(("a", pod.qos, stage),
                                   DDSketch()).insert(v)
                ref.setdefault(("a", pod.qos, "e2e"), DDSketch()).insert(
                    (start - (start - float(w))) + (solve_s + commit_s))
            refs.append(ref)
        return refs, base

    @staticmethod
    def _table(doc):
        return {(r["tenant"], r["qos"], r["stage"]): canon(
            DDSketch.from_doc(r["sketch"])) for r in doc["series"]}

    @staticmethod
    def _want(ref):
        return {k: canon(sk) for k, sk in ref.items()}

    @pytest.mark.parametrize("mixed_qos", [False, True])
    def test_no_bounds_reads_what_it_read_before(self, mixed_qos):
        led = JourneyLedger()
        refs, _ = self._rounds(led, 7, seed=1, mixed_qos=mixed_qos,
                               one_by_one=(2,))
        doc = led.snapshot_doc()
        assert "rounds" not in doc
        assert self._table(doc) == self._want(refs[-1])
        report = led.report()
        assert "rounds" not in report
        for row in report["series"]:
            ref = refs[-1][(row["tenant"], row["qos"], row["stage"])]
            assert row["count"] == ref.count
            assert row["p50_s"] == ref.quantile(0.5)
            assert row["p99_s"] == ref.quantile(0.99)
            assert row["mean_s"] == pytest.approx(ref.mean())
            assert row["max_s"] == pytest.approx(ref.max_value)

    @pytest.mark.parametrize("mixed_qos", [False, True])
    def test_bounds_over_every_round_equal_no_bounds(self, mixed_qos):
        led = JourneyLedger()
        self._rounds(led, 6, seed=2, mixed_qos=mixed_qos, one_by_one=(0, 4))
        whole = led.snapshot_doc()
        cut = led.snapshot_doc(since_perf=0.0, until_perf=1e12)
        assert cut.pop("rounds") == 6
        assert self._table(cut) == self._table(whole)
        assert led.report(since_perf=0.0)["rounds"] == 6
        for a, b in zip(led.report(since_perf=0.0)["series"],
                        led.report()["series"]):
            assert {k: a[k] for k in a if k != "mean_s"} == {
                k: b[k] for k in b if k != "mean_s"}
            assert a["mean_s"] == pytest.approx(b["mean_s"])

    def test_a_range_holds_the_rounds_committed_in_it(self):
        led = JourneyLedger()
        refs, base = self._rounds(led, 8, seed=3)
        # rounds 0..2 commit before base + 3
        early = led.snapshot_doc(until_perf=base + 2.9)
        assert early["rounds"] == 3
        assert self._table(early) == self._want(refs[3])
        report = led.report(since_perf=base + 2.9, until_perf=base + 5.9)
        assert report["rounds"] == 3
        counts = {r["stage"]: r["count"] for r in report["series"]}
        want = refs[6][("a", 0, "e2e")].count - refs[3][("a", 0, "e2e")].count
        assert counts == {s: want for s in
                          ("e2e", "queue_wait", "solve", "commit")}
        assert led.report(since_perf=base + 100.0) == {
            "enabled": True, "alpha": RELATIVE_ACCURACY, "series": [],
            "rounds": 0}

    def test_two_disjoint_ranges_merge_to_the_whole(self):
        led = JourneyLedger()
        refs, base = self._rounds(led, 9, seed=4, mixed_qos=True)
        first = led.snapshot_doc(until_perf=base + 3.9)
        second = led.snapshot_doc(since_perf=base + 3.9)
        assert first["rounds"] + second["rounds"] == 9
        merged = merge_snapshot_rows(first["series"] + second["series"])
        assert {k: canon(sk) for k, sk in merged.items()} == (
            self._want(refs[-1]))

    def test_a_read_between_rounds_changes_nothing(self):
        """Digestion is lazy: a reader in the middle of the run folds
        what is staged, the rest folds later, the sum is the same."""
        read, unread = JourneyLedger(), JourneyLedger()
        refs, _ = self._rounds(read, 5, seed=5, read_after=(0, 2))
        self._rounds(unread, 5, seed=5)
        assert len(read._staged) == 2 and len(unread._staged) == 5
        for led in (read, unread):
            cut = led.snapshot_doc(since_perf=0.0)
            assert cut.pop("rounds") == 5
            assert self._table(cut) == self._want(refs[-1])
            assert self._table(led.snapshot_doc()) == self._want(refs[-1])

    def test_the_ring_is_bounded(self, monkeypatch):
        monkeypatch.setattr(JourneyLedger, "_SLICES_MAX", 5)
        led = JourneyLedger()
        refs, base = self._rounds(led, 12, seed=6)
        cut = led.snapshot_doc(since_perf=0.0)
        assert cut["rounds"] == 5 and len(led._slices) == 5
        # the five newest rounds; the cumulative sketches still hold all
        want = refs[12][("a", 0, "e2e")].count - refs[7][("a", 0, "e2e")].count
        e2e = [r for r in cut["series"] if r["stage"] == "e2e"][0]
        assert e2e["sketch"]["count"] == want
        assert self._table(led.snapshot_doc()) == self._want(refs[-1])

    def test_the_inline_digest_keeps_its_slices(self, monkeypatch):
        """No reader ever comes: at _STAGED_MAX staged rounds the bind
        path digests inline, and the slices are there afterwards."""
        monkeypatch.setattr(JourneyLedger, "_STAGED_MAX", 4)
        led = JourneyLedger()
        self._rounds(led, 9, seed=7)
        assert len(led._staged) == 1 and len(led._slices) == 8
        assert led.report(since_perf=0.0)["rounds"] == 9

    def test_disabling_clears_the_slices(self):
        led = JourneyLedger()
        self._rounds(led, 3, seed=8)
        led.report()
        assert len(led._slices) == 3
        led.set_enabled(False)
        assert len(led._slices) == 0

    def test_debug_latency_last_s(self):
        sched, svc = _assemble()
        svc.add_pod("p1", np.asarray(
            resource_vector(cpu=1_000, memory=1_024), np.int32))
        sched.schedule_round()
        body = debug_latency_body(sched, {"last_s": "60"})
        assert body["last_s"] == 60.0 and body["rounds"] == 1
        assert any(r["stage"] == "e2e" and r["count"] == 1
                   for r in body["series"])
        # a round older than the range is not in it
        stale = debug_latency_body(sched, {"last_s": 1e-9})
        assert stale["rounds"] == 0 and stale["series"] == []
        assert "rounds" not in debug_latency_body(sched, {})
        for bad in ("soon", "0", "-3"):
            with pytest.raises(DebugApiError) as ei:
                debug_latency_body(sched, {"last_s": bad})
            assert ei.value.status == 400

    def test_both_surfaces_serve_last_s(self):
        from koordinator_tpu.scheduler.services import DebugService

        sched, svc = _assemble()
        svc.add_pod("p1", np.asarray(
            resource_vector(cpu=1_000, memory=1_024), np.int32))
        sched.schedule_round()
        status, body = DebugService(sched).handle(
            "/debug/latency", {"last_s": "30"})
        assert status == 200 and body["rounds"] == 1
        status, body = DebugService(sched).handle(
            "/debug/latency", {"last_s": "never"})
        assert status == 400 and "error" in body


class TestBitIdentity:
    """THE acceptance criterion: KOORD_JOURNEY=0 must not change one
    scheduling decision or quota charge."""

    def _run(self, enabled: bool):
        journey.LEDGER.set_enabled(enabled)
        journey.LEDGER.reset_for_tests()
        from koordinator_tpu.api.resources import (
            NUM_RESOURCE_DIMS,
            ResourceDim,
        )
        from koordinator_tpu.quota.tree import UNBOUNDED, QuotaTree

        mx = np.full(NUM_RESOURCE_DIMS, UNBOUNDED, np.int64)
        mx[ResourceDim.CPU] = 8_000
        tree = QuotaTree(np.asarray(
            resource_vector(cpu=32_000, memory=131_072), np.int64))
        tree.add("team", min=np.zeros(NUM_RESOURCE_DIMS, np.int64),
                 max=mx)
        snap = ClusterSnapshot(capacity=16)
        sched = Scheduler(snap, quota_tree=tree)
        svc = StateSyncService()
        svc.attach_binding(SchedulerBinding(sched))
        svc.upsert_node("n1", np.asarray(
            resource_vector(cpu=16_000, memory=65_536), np.int32))
        svc.upsert_node("n2", np.asarray(
            resource_vector(cpu=4_000, memory=8_192), np.int32))
        for i in range(12):
            svc.add_pod(
                f"p{i}", np.asarray(resource_vector(
                    cpu=1_000 + 100 * (i % 3), memory=1_024), np.int32),
                priority=i % 4, quota="team", qos=i % 3,
                arrival_ts=time.time())
        assignments = {}
        for _ in range(3):
            assignments.update(sched.schedule_round().assignments)
        used = np.asarray(tree.nodes["team"].used).tolist()
        return assignments, used

    def test_decisions_and_quota_charges_identical_on_vs_off(self):
        on_assign, on_used = self._run(True)
        off_assign, off_used = self._run(False)
        assert on_assign == off_assign
        assert on_used == off_used
        assert on_assign, "round placed nothing — vacuous comparison"

    def test_a_windowed_read_between_rounds_moves_no_decision(self):
        """ISSUE 34: the slices are made in the lazy digest, off the
        scheduling path; a reader that cuts the ledger by time between
        the rounds changes no decision either."""
        plain_assign, plain_used = self._run(True)
        orig = Scheduler.schedule_round

        def round_then_read(sched, *a, **kw):
            out = orig(sched, *a, **kw)
            journey.LEDGER.report(since_perf=0.0)
            return out

        Scheduler.schedule_round = round_then_read
        try:
            read_assign, read_used = self._run(True)
        finally:
            Scheduler.schedule_round = orig
        off_assign, off_used = self._run(False)
        assert read_assign == plain_assign == off_assign
        assert read_used == plain_used == off_used


class TestDebugSurface:
    def test_body_reports_recorded_series(self):
        sched, svc = _assemble()
        svc.add_pod("p1", np.asarray(
            resource_vector(cpu=1_000, memory=1_024), np.int32))
        sched.schedule_round()
        body = debug_latency_body(sched, {})
        assert body["enabled"] is True
        assert body["stages"] == list(journey.STAGES)
        assert any(r["stage"] == "e2e" for r in body["series"])

    def test_unknown_tenant_is_typed_400(self):
        sched, _svc = _assemble()
        with pytest.raises(DebugApiError) as ei:
            debug_latency_body(sched, {"tenant": "absent"})
        assert ei.value.status == 400

    def test_disabled_ledger_is_typed_501(self):
        sched, _svc = _assemble()
        journey.LEDGER.set_enabled(False)
        with pytest.raises(DebugApiError) as ei:
            debug_latency_body(sched, {})
        assert ei.value.status == 501

    def test_debug_service_serves_the_shared_builder(self):
        from koordinator_tpu.scheduler.services import DebugService

        sched, svc = _assemble()
        svc.add_pod("p1", np.asarray(
            resource_vector(cpu=1_000, memory=1_024), np.int32))
        sched.schedule_round()
        dbg = DebugService(sched)
        status, body = dbg.handle("/debug/latency", {})
        assert status == 200 and body["enabled"] is True
        status, body = dbg.handle("/debug/latency", {"tenant": "nope"})
        assert status == 400 and "error" in body


class TestSloIntegration:
    def test_pod_e2e_p99_spec_ships_over_the_journey_gauge(self):
        """The ledger is a first-class SloMonitor window source: the
        shipped gauge SLO burns from the sketch-backed e2e p99 gauge,
        sliced to the {q=0.99, stage=e2e} series."""
        from koordinator_tpu.slo_monitor import KIND_GAUGE, default_specs

        spec = {s.name: s for s in default_specs()}["pod_e2e_p99"]
        assert spec.kind == KIND_GAUGE
        assert spec.metric == "koord_scheduler_pod_journey_latency_seconds"
        assert dict(spec.label_filter) == {"q": "0.99", "stage": "e2e"}
        assert spec.threshold == pytest.approx(0.2)
        # any tenant's e2e-p99 series counts; other stages never do
        assert spec.matches_labels(
            {"tenant": "t0", "qos": "1", "stage": "e2e", "q": "0.99"})
        assert not spec.matches_labels(
            {"tenant": "t0", "qos": "1", "stage": "solve", "q": "0.99"})
