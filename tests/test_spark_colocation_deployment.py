"""koord-manager beside koord-scheduler (both assembled by ``MAINS``) over a
real socket and a stepped report clock: every tick's patches equal the plain
reference's (set and values), the scheduler's allocatable rows equal the last
patch of every node, no BE pod binds over the batch allocatable of its round,
the webhook writes what the reference writes, the sync rule's scenarios one by
one, and the loop's spans and counters.
"""

from __future__ import annotations

import copy
import json
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.context import Context  # noqa: E402
from benchmarks.layers import colo_patches_per_frame  # noqa: E402
from benchmarks.reference import colocation as reference  # noqa: E402
from benchmarks.spans import Spans  # noqa: E402
from koordinator_tpu import metrics, timeline  # noqa: E402
from koordinator_tpu.api import crds  # noqa: E402
from koordinator_tpu.api.resources import ResourceDim  # noqa: E402
from koordinator_tpu.manager.noderesource_controller import (  # noqa: E402
    NodeRecord,
    NodeResourceController,
)
from koordinator_tpu.manager.sloconfig import ColocationConfig  # noqa: E402
from koordinator_tpu.manager.webhook import PodMutatingWebhook  # noqa: E402

CYCLES = 9


def small_config() -> tuple[dict, dict]:
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "spark-colocation-10k.json")) as f:
        config = json.load(f)
    # the load swings through most of its range inside the test's ticks, so
    # that batch allocatable drifts over and under the threshold and shrinks
    # under bound BE pods
    config["value_ranges"]["node_load"] = {"amplitude": 0.4,
                                           "period_cycles": 16}
    config["clock"]["watch_chunk"] = 64
    return config, {"nodes": 128, "ls_pods": 1500, "standing": 0}


@pytest.fixture(scope="module")
def run(tmp_path_factory):
    """The assembled pair, driven by the benchmark's own deployment and
    traffic kind through set-up and ``CYCLES`` cycles."""
    from benchmarks.deployments import colocated_manager
    from benchmarks.kinds import colocation_closed as kind

    config, sizes = small_config()
    params = {"fill_waves": 2, "jobs_per_cycle": 3,
              "warm_overflow_standing": 0, "settle_min": 1,
              "settle_window": 1, "settle_tolerance": 1.0, "settle_max": 1,
              "paths": {"round": "full"}}
    cwd = os.getcwd()
    os.chdir(tmp_path_factory.mktemp("colo"))
    dep = colocated_manager.Deployment(config, sizes, 7, "run")
    spans = Spans(False)
    def counters() -> dict:
        out = {labels["reason"]: value for labels, value
               in metrics.colocation_sync_reason_total.items()}
        out["watched"] = metrics.colocation_watch_events_total.value()
        out["patches"] = metrics.colocation_patches_total.value()
        out["frames"] = metrics.colocation_push_frames_total.value()
        out["frames_sent"] = metrics.sync_delta_frames_sent_total.value()
        out["events_sent"] = metrics.sync_delta_events_sent_total.value()
        return out

    before = counters()
    try:
        state = kind.setup(dep, params, spans)
        dep.books.window_open = True
        t_open = time.perf_counter()
        cycles = [kind.cycle(dep, params, spans) for _ in range(CYCLES - 1)]
        # the last cycle's wave by itself: what the watch was sent for it
        dep.step_clock()
        dep.catch_up()
        sent = counters()
        dep.usage_wave(spans)
        wave = {k: counters()[k] - sent[k]
                for k in ("frames_sent", "events_sent", "watched")}
        dep.now -= dep.config["clock"]["report_interval_seconds"]
        dep.cycle -= 1
        cycles.append(kind.cycle(dep, params, spans))
        compared = dep.verify()
        ticks = reference.replay_ticks(dep.capacity, dep.tick_log,
                                       dep.colocation)
        held = dep.held()
        docs = timeline.RECORDER.cycles(64)
        # the suite zeroes every counter between tests: read them here
        counted = {k: v - before.get(k, 0) for k, v in counters().items()}
        per_frame = colo_patches_per_frame.read(Context(
            spans=spans, t_open=t_open, t_close=float("inf"),
            timeline_docs=docs))
    finally:
        dep.close()
        os.chdir(cwd)
    return {"dep": dep, "state": state, "cycles": cycles,
            "compared": compared, "ticks": ticks, "held": held, "docs": docs,
            "counted": counted, "wave": wave, "per_frame": per_frame}


def test_every_compared_number_reads_zero(run):
    assert run["compared"] == dict.fromkeys(run["compared"], 0)
    assert {"patch_set_mismatch", "patch_value_mismatch",
            "allocatable_state_mismatch", "batch_overcommit_at_bind",
            "bind_on_squeezed_node", "admission_mismatch",
            "be_pod_charged_off_batch_dims"} <= set(run["compared"])


def test_patches_equal_the_reference_tick_by_tick(run):
    dep, ticks = run["dep"], run["ticks"]
    assert len(ticks) >= 8 + dep.config["clock"]["bring_up_intervals"]
    for logged, want in zip(dep.tick_log, ticks):
        assert np.array_equal(logged["patched"], want["patched"])
        assert np.array_equal(logged["stored"],
                              np.maximum(want["standing"], 0))
        assert logged["pushed"] == int(want["patched"].sum())


def test_a_report_wave_reaches_the_manager_in_runs(run):
    """The manager's watch is behind the in-process reporter by
    construction (one interpreter): a wave goes out as fewer DELTA frames
    than reports, every report is applied, and the ticks patch what the
    reference patches (above)."""
    nodes, wave = run["dep"].sizes["nodes"], run["wave"]
    assert wave["watched"] == nodes
    # the counters sum over connections: the deployment's own client,
    # hung up before the wave, stays listed for the first few events
    assert nodes <= wave["events_sent"] < 2 * nodes
    assert 1 <= wave["frames_sent"] < nodes
    counted = run["counted"]
    assert counted["frames_sent"] < counted["events_sent"]
    assert run["dep"].manager.component.sync.gaps == 0


@pytest.mark.parametrize("reason", ["first", "time_gap", "diff", None])
def test_the_ticks_hold_every_scenario_of_the_sync_rule(run, reason):
    """A first sync, a time-gap sync, drift above the threshold (``diff``)
    and below it (a fresh node left alone) all occur."""
    seen = {r for tick in run["ticks"] for r in tick["reasons"]}
    assert reason in seen


def test_batch_allocatable_shrinks_under_bound_be_pods(run):
    """Some node's batch CPU was patched down while BE pods stood on it,
    and a node squeezed that way took no BE pod."""
    dep, ticks = run["dep"], run["ticks"]
    shrank = 0
    for entry in dep.round_log[1:]:
        before = ticks[entry["tick"] - 1]["standing"][:, 0]
        after = ticks[entry["tick"]]["standing"][:, 0]
        shrank += int(((after < before)
                       & (entry["requested"][:, 0] > 0)).sum())
    assert shrank > 0
    assert max(c["squeezed"] for c in run["cycles"]) > 0
    assert run["compared"]["bind_on_squeezed_node"] == 0
    assert run["compared"]["batch_overcommit_at_bind"] == 0


def test_scheduler_rows_equal_the_last_patch_of_every_node(run):
    dep, last = run["dep"], run["ticks"][-1]["standing"]
    rows = np.stack([run["held"]["alloc"][name]
                     for name in dep.books.node_names])
    assert np.array_equal(rows[:, dep.written], last)
    assert np.array_equal(rows[:, dep.own], dep.capacity)
    assert (last[:, :2].sum(axis=0) > 0).all()


def test_spark_pods_bound_as_be_on_the_batch_dimensions_only(run):
    dep, held = run["dep"], run["held"]
    spark = [p for p, s in dep.serial_of.items()
             if dep.p_be[s] and p in dep.books.bound]
    assert len(spark) > 50
    for pod in spark:
        charged = np.asarray(held["bound_requests"][pod])
        assert charged[dep.batch].all()
        assert not np.delete(charged, dep.batch).any()
    assert sum(c["arrived"] for c in run["cycles"]) == len(
        dep.books.offered)


def span_total(docs, name):
    segs = [s for doc in docs for s in doc["segments"]
            if s["name"] == name and "n" in s]
    return sum(s["n"] for s in segs), {s["parent"] for s in segs}


def test_spans_and_counters_carry_the_right_members(run):
    dep, docs = run["dep"], run["docs"]
    nodes = dep.sizes["nodes"]
    ticks, parents = span_total(docs, "colo.tick")
    # the ring keeps the newest docs: at least the measured cycles' ticks
    assert ticks >= CYCLES and parents == {""}
    for name in ("colo.records", "colo.reconcile"):
        members, parents = span_total(docs, name)
        assert members == pytest.approx(ticks * nodes)
        assert parents == {"colo.tick"}
    solves, parents = span_total(docs, "colo.solve")
    assert solves == pytest.approx(ticks) and parents == {"colo.reconcile"}
    pushed, parents = span_total(docs, "colo.push")
    assert parents == {"colo.tick"}
    assert pushed == pytest.approx(
        sum(t["pushed"] for t in dep.tick_log[-int(round(ticks)):]))
    # a tick's patches leave in frames: 128 nodes, so one frame a tick that
    # has something to say, each one synchronous STATE_PUSH
    recent = dep.tick_log[-int(round(ticks)):]
    in_frames, parents = span_total(docs, "colo.push.frame")
    assert in_frames == pytest.approx(pushed) and parents == {"colo.push"}
    calls = [s for doc in docs for s in doc["segments"]
             if s["name"] == "rpc.call.STATE_PUSH"
             and s["parent"] == "colo.push.frame"]
    assert sum(s["n"] for s in calls) == pytest.approx(
        sum(t["pushed"] > 0 for t in recent))
    admitted, _ = span_total(docs, "colo.admit")
    assert admitted >= sum(c["arrived"] for c in run["cycles"])
    watched, parents = span_total(docs, "colo.watch")
    assert watched > 0
    assert parents <= {"sync.node_usage", "sync.node_allocatable",
                       "sync.node_upsert"}
    # counters: every patch has a reason, every node delta is counted
    counted = run["counted"]
    patches = sum(t["pushed"] for t in dep.tick_log)
    assert counted["patches"] == patches
    assert counted["frames"] == sum(t["pushed"] > 0 for t in dep.tick_log)
    # the benchmark's reader: patches / frames over the measured cycles
    window = dep.tick_log[-CYCLES:]
    assert run["per_frame"] == pytest.approx(
        sum(t["pushed"] for t in window)
        / sum(t["pushed"] > 0 for t in window))
    assert 1 < run["per_frame"] <= nodes
    for reason in ("first", "time_gap", "diff"):
        assert counted[reason] == sum(tick["reasons"].count(reason)
                                      for tick in run["ticks"])
    assert "degraded" not in counted
    # the watch applied the snapshot's upserts, every report of every
    # cycle and the echo of every patch
    assert counted["watched"] >= nodes * (1 + CYCLES) + patches


# -- the sync rule, scenario by scenario ----------------------------------------

def record_synced_at(t: float, batch_cpu: int = 10_000) -> NodeRecord:
    return NodeRecord(
        name="n", cpu_capacity_milli=32_000, mem_capacity_mib=65_536,
        last_batch_cpu=batch_cpu, last_batch_mem=20_000, last_mid_cpu=0,
        last_mid_mem=0, last_device_resources={}, last_sync_time=t)


@pytest.mark.parametrize("record,now,new_cpu,want", [
    # never synced: the first sync, whatever the values
    (NodeRecord(name="n", cpu_capacity_milli=32_000,
                mem_capacity_mib=65_536), 1_000.0, 10_000, "first"),
    # the last sync is older than updateTimeThresholdSeconds (strictly)
    (record_synced_at(1_000.0), 1_301.0, 10_000, "time_gap"),
    (record_synced_at(1_000.0), 1_300.0, 10_000, None),
    # a resource moved by more than resourceDiffThreshold
    (record_synced_at(1_000.0), 1_060.0, 11_001, "diff"),
    (record_synced_at(1_000.0), 1_060.0, 8_999, "diff"),
    # drift under the threshold: left alone
    (record_synced_at(1_000.0), 1_060.0, 11_000, None),
    (record_synced_at(1_000.0), 1_060.0, 10_000, None),
])
def test_sync_reason_scenarios_equal_the_reference(record, now, new_cpu, want):
    controller = NodeResourceController(ColocationConfig(enable=True))
    took = controller._sync_reason(record, now, new_cpu, 20_000, 0, 0, {})
    assert took == want
    last = (None if record.last_batch_cpu < 0 else np.array(
        [record.last_batch_cpu, record.last_batch_mem, 0, 0]))
    cfg = dict(reference.DEFAULTS, enable=True)
    assert reference.sync_reason(last, record.last_sync_time, now,
                                 np.array([new_cpu, 20_000, 0, 0]),
                                 cfg) == want


def test_reconcile_stamps_the_sync_time_and_resyncs_a_node_at_rest():
    """A node whose usage sits still is patched again once the time gap
    has passed, and not before."""
    now = [1_000.0]
    controller = NodeResourceController(ColocationConfig(enable=True),
                                        clock=lambda: now[0])
    record = NodeRecord(name="n", cpu_capacity_milli=32_000,
                        mem_capacity_mib=65_536)
    patched = []
    for _ in range(8):
        record.metric = crds.NodeMetricStatus(
            update_time=now[0],
            node_usage=crds.ResourceUsage(cpu_milli=4_000,
                                          memory_bytes=8 << 30),
            system_usage=crds.ResourceUsage(cpu_milli=1_000,
                                            memory_bytes=1 << 30))
        patched.append(len(controller.reconcile([record])))
        now[0] += 60.0
    assert patched == [1, 0, 0, 0, 0, 0, 1, 0]
    assert record.last_sync_time == 1_360.0


# -- the webhook against the reference -------------------------------------------

PROFILE = {"name": "colocation-profile-example",
           "pod_selector": {"koordinator.sh/enable-colocation": "true"},
           "qos": "BE", "priority": 5500,
           "scheduler_name": "koord-scheduler"}


def random_pod(rng, i: int) -> dict:
    cpu = [f"{int(rng.integers(100, 8_000))}m", str(int(rng.integers(1, 8))),
           float(rng.integers(1, 16)) / 2][int(rng.integers(3))]
    mem = [f"{int(rng.integers(128, 8_192))}Mi",
           f"{int(rng.integers(1, 16))}Gi",
           int(rng.integers(1, 1 << 33))][int(rng.integers(3))]
    labels = {"app": f"a{i}"}
    if rng.random() < 0.7:
        labels["koordinator.sh/enable-colocation"] = (
            "true" if rng.random() < 0.9 else "false")
    if rng.random() < 0.2:
        labels[reference.LABEL_QOS] = ["BE", "LS"][int(rng.integers(2))]
    resources = {"requests": {"cpu": cpu, "memory": mem}}
    if rng.random() < 0.7:
        resources["limits"] = {"cpu": cpu, "memory": mem}
    spec = {"containers": [{"name": "c", "resources": resources}]}
    if rng.random() < 0.3:
        spec["priority"] = int(rng.choice([5_200, 7_500, 9_500]))
    return {"metadata": {"name": f"p{i}", "namespace": "default",
                         "labels": labels}, "spec": spec}


@pytest.mark.parametrize("seed", [1, 2, 3, 4])
def test_webhook_writes_what_the_reference_writes(seed):
    rng = np.random.default_rng(seed)
    webhook = PodMutatingWebhook()
    webhook.set_profiles([crds.ClusterColocationProfile(
        name=PROFILE["name"], pod_selector=PROFILE["pod_selector"],
        qos_class=PROFILE["qos"], koordinator_priority=PROFILE["priority"],
        scheduler_name=PROFILE["scheduler_name"])])
    dims = {"count": len(ResourceDim), "cpu": int(ResourceDim.CPU),
            "memory": int(ResourceDim.MEMORY),
            "batch_cpu": int(ResourceDim.BATCH_CPU),
            "batch_memory": int(ResourceDim.BATCH_MEMORY)}
    translated = 0
    for i in range(200):
        pod = random_pod(rng, i)
        want = reference.admit(pod, PROFILE)
        took = webhook.mutate(copy.deepcopy(pod))
        took["metadata"].pop("annotations", None)
        assert took == want
        vector = reference.request_vector(took, dims)
        translated += bool(vector[dims["batch_cpu"]])
        assert bool(vector[dims["batch_cpu"]]) != bool(vector[dims["cpu"]])
    assert 40 < translated < 200
