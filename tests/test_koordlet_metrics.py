"""metriccache + metricsadvisor + statesinformer tests against a fake kernel fs."""

import os

import pytest

from koordinator_tpu.api.qos import QoSClass
from koordinator_tpu.koordlet import metriccache as mc
from koordinator_tpu.koordlet import metricsadvisor as ma
from koordinator_tpu.koordlet.statesinformer import (
    ContainerMeta, NodeInfo, PodMeta, StatesInformer,
)
from koordinator_tpu.koordlet.system import cgroup as cg
from koordinator_tpu.koordlet.system.config import make_test_config
from tests.test_koordlet_system import write_cgroup_file


class FakeClock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t

    def tick(self, dt):
        self.t += dt


@pytest.fixture
def clock():
    return FakeClock()


@pytest.fixture
def cfg(tmp_path):
    return make_test_config(tmp_path)


def make_pod(uid="pod-1", qos=QoSClass.LS, kube_qos="burstable", **kw):
    return PodMeta(
        uid=uid, name=uid, namespace="default", qos_class=qos,
        kube_qos=kube_qos, **kw,
    )


class TestMetricCache:
    def test_ring_window_and_aggregates(self, clock):
        cache = mc.MetricCache(capacity_per_series=8, clock=clock)
        for i in range(12):  # wraps: only last 8 retained
            cache.append(mc.NODE_CPU_USAGE, float(i), ts=1000.0 + i)
        result = cache.query(mc.NODE_CPU_USAGE, start=0, end=2000)
        assert result.count == 8
        assert result.latest() == 11.0
        assert result.max() == 11.0
        assert result.avg() == pytest.approx(sum(range(4, 12)) / 8)
        # windowed subset
        sub = cache.query(mc.NODE_CPU_USAGE, start=1008, end=1010)
        assert sub.count == 3

    def test_percentiles_lower_interpolation(self, clock):
        cache = mc.MetricCache(clock=clock)
        for i in range(1, 101):
            cache.append(mc.NODE_CPU_USAGE, float(i), ts=1000.0 + i)
        result = cache.query(mc.NODE_CPU_USAGE, start=0, end=2000)
        ps = result.percentiles([0.5, 0.9, 0.95, 0.99])
        assert ps[0.5] == 50.0
        assert ps[0.99] == 99.0

    def test_labels_and_gc(self, clock):
        cache = mc.MetricCache(clock=clock)
        cache.append(mc.POD_CPU_USAGE, 1.0, {"pod_uid": "a"})
        cache.append(mc.POD_CPU_USAGE, 2.0, {"pod_uid": "b"})
        assert len(cache.series_labels(mc.POD_CPU_USAGE)) == 2
        dropped = cache.gc(keep_pod_uids={"a"})
        assert dropped == 1
        assert cache.query(mc.POD_CPU_USAGE, {"pod_uid": "b"}).empty

    def test_kv(self):
        cache = mc.MetricCache()
        cache.set_kv("numa", {"nodes": 2})
        assert cache.get_kv("numa") == {"nodes": 2}


class TestMetricCacheRetentionAndDownsampling:
    """Retention/downsampling boundaries (ISSUE 5 satellite): only the
    happy path was covered before."""

    def test_exact_horizon_sample_kept_one_older_evicted(self, clock):
        cache = mc.MetricCache(clock=clock, retention_sec=60.0)
        clock.t = 1060.0
        cache.append(mc.NODE_CPU_USAGE, 1.0, ts=999.9)    # one older
        cache.append(mc.NODE_CPU_USAGE, 2.0, ts=1000.0)   # exactly at horizon
        cache.append(mc.NODE_CPU_USAGE, 3.0, ts=1030.0)
        res = cache.query(mc.NODE_CPU_USAGE, start=0, end=2000)
        # the sample AT now - retention is served; the one strictly
        # older is not, even though the ring still physically holds it
        assert res.count == 2
        assert sorted(res.values.tolist()) == [2.0, 3.0]

    def test_retention_moves_with_the_clock(self, clock):
        cache = mc.MetricCache(clock=clock, retention_sec=60.0)
        clock.t = 1000.0
        cache.append(mc.NODE_CPU_USAGE, 1.0, ts=1000.0)
        assert cache.query(mc.NODE_CPU_USAGE, start=0).count == 1
        clock.tick(61.0)
        assert cache.query(mc.NODE_CPU_USAGE, start=0).count == 0

    def test_no_retention_serves_everything(self, clock):
        cache = mc.MetricCache(clock=clock)   # retention_sec=None
        cache.append(mc.NODE_CPU_USAGE, 1.0, ts=1.0)
        clock.t = 10_000.0
        assert cache.query(mc.NODE_CPU_USAGE, start=0).count == 1

    def test_empty_window_aggregates_are_sentinels_not_nan(self, clock):
        import math

        cache = mc.MetricCache(clock=clock)
        cache.append(mc.NODE_CPU_USAGE, 5.0, ts=1000.0)
        res = cache.query(mc.NODE_CPU_USAGE, start=2000, end=3000)
        assert res.empty and res.count == 0
        for value in (res.avg(), res.latest(), res.first(), res.max(),
                      res.percentile(0.99), res.duration_seconds()):
            assert value == 0.0
            assert not math.isnan(value)
        # a never-written series behaves identically
        ghost = cache.query("never_written")
        assert ghost.empty and not math.isnan(ghost.avg())


class TestMetricCacheLongHorizonTier:
    """Two-tier downsampling horizon (ISSUE 9 satellite): samples aging
    past ``downsample_after_sec`` move into a bounded cold ring at
    mean-per-bin resolution instead of being silently evicted by hot
    wraparound — hours-long soaks stay memory-bounded AND keep history.
    """

    def _cache(self, clock, **kw):
        kw.setdefault("downsample_after_sec", 60.0)
        kw.setdefault("downsample_resolution_sec", 10.0)
        return mc.MetricCache(clock=clock, **kw)

    def test_exact_horizon_kept_hot_one_older_downsampled(self, clock):
        cache = self._cache(clock)
        cache.append(mc.NODE_CPU_USAGE, 1.0, ts=999.9)    # one older
        cache.append(mc.NODE_CPU_USAGE, 2.0, ts=1000.0)   # exactly AT
        cache.append(mc.NODE_CPU_USAGE, 3.0, ts=1030.0)
        clock.t = 1060.0
        cache.compact()
        # the horizon sample and newer stay in the hot ring at full
        # resolution; the strictly-older one moved to the cold tier
        key = mc._series_key(mc.NODE_CPU_USAGE, None)
        hot_ts, hot_vals = cache._series[key].chronological()
        assert hot_vals.tolist() == [2.0, 3.0]
        # ... but the QUERY still serves all three (cold merged in)
        res = cache.query(mc.NODE_CPU_USAGE, start=0, end=2000)
        assert sorted(res.values.tolist()) == [1.0, 2.0, 3.0]

    def test_drained_samples_downsample_to_bin_means(self, clock):
        cache = self._cache(clock)
        # bin [1000, 1010): three samples -> ONE cold sample at their mean
        for ts, v in ((1001.0, 1.0), (1004.0, 2.0), (1007.0, 9.0)):
            cache.append(mc.NODE_CPU_USAGE, v, ts=ts)
        # a later bin's sample finalizes the pending one
        cache.append(mc.NODE_CPU_USAGE, 5.0, ts=1015.0)
        clock.t = 1200.0
        cache.compact()
        res = cache.query(mc.NODE_CPU_USAGE, start=0, end=2000)
        assert res.count == 2          # two bins, one sample each
        assert sorted(res.values.tolist()) == [4.0, 5.0]   # mean(1,2,9)=4
        assert res.avg() == pytest.approx(4.5)

    def test_memory_stays_bounded_over_a_long_run(self, clock):
        cache = self._cache(clock, capacity_per_series=64)
        # simulate hours: 10x the hot capacity at 1s cadence
        for i in range(640):
            clock.t = 1000.0 + i
            cache.append(mc.NODE_CPU_USAGE, float(i))
        key = mc._series_key(mc.NODE_CPU_USAGE, None)
        assert cache._series[key].count <= 64
        tier = cache._cold[key]
        assert tier.ring.count <= 64
        # history survived in downsampled form: the window covers far
        # more than the hot ring alone could (64 raw + cold bins)
        res = cache.query(mc.NODE_CPU_USAGE, start=0, end=5000)
        assert res.count > 64
        assert res.duration_seconds() > 500.0

    def test_append_triggers_compaction_lazily(self, clock):
        cache = self._cache(clock)
        cache.append(mc.NODE_CPU_USAGE, 1.0, ts=1000.0)
        # an append a full bin past the horizon compacts without an
        # explicit compact() call
        clock.t = 1075.0
        cache.append(mc.NODE_CPU_USAGE, 2.0, ts=1075.0)
        key = mc._series_key(mc.NODE_CPU_USAGE, None)
        hot_ts, hot_vals = cache._series[key].chronological()
        assert hot_vals.tolist() == [2.0]
        assert key in cache._cold

    def test_disabled_tier_keeps_old_behavior(self, clock):
        cache = mc.MetricCache(clock=clock)   # no downsample horizon
        cache.append(mc.NODE_CPU_USAGE, 1.0, ts=1.0)
        clock.t = 100_000.0
        cache.compact()                        # no-op
        assert cache.query(mc.NODE_CPU_USAGE, start=0).count == 1
        assert not cache._cold

    def test_delete_series_drops_cold_tier_too(self, clock):
        cache = self._cache(clock)
        cache.append(mc.POD_CPU_USAGE, 1.0, {"pod_uid": "a"}, ts=1000.0)
        clock.t = 1200.0
        cache.compact()
        cache.delete_series(mc.POD_CPU_USAGE, {"pod_uid": "a"})
        assert not cache._cold
        assert cache.query(mc.POD_CPU_USAGE, {"pod_uid": "a"}).empty

    def test_downsample_mean_per_bin(self, clock):
        cache = mc.MetricCache(clock=clock)
        for i in range(10):   # ts 1000..1009, values 0..9
            cache.append(mc.NODE_CPU_USAGE, float(i), ts=1000.0 + i)
        res = cache.query(mc.NODE_CPU_USAGE, start=0, end=2000)
        down = res.downsample(5.0)
        assert down.count == 2
        assert down.values.tolist() == [
            pytest.approx(2.0), pytest.approx(7.0)]
        assert down.ts.tolist() == [
            pytest.approx(1002.0), pytest.approx(1007.0)]
        # aggregates keep working on the downsampled view
        assert down.avg() == pytest.approx(4.5)

    def test_downsample_noop_cases(self, clock):
        cache = mc.MetricCache(clock=clock)
        empty = cache.query(mc.NODE_CPU_USAGE)
        assert empty.downsample(5.0) is empty
        cache.append(mc.NODE_CPU_USAGE, 1.0, ts=1000.0)
        res = cache.query(mc.NODE_CPU_USAGE, start=0, end=2000)
        assert res.downsample(0.0) is res


def write_proc(cfg, used_jiffies, mem_used_kb=400, mem_total_kb=1000):
    os.makedirs(cfg.proc_root, exist_ok=True)
    with open(cfg.proc_path("stat"), "w") as f:
        f.write(f"cpu  {used_jiffies} 0 0 800 0 0 0 0 0 0\n")
    with open(cfg.proc_path("meminfo"), "w") as f:
        f.write(
            f"MemTotal: {mem_total_kb} kB\n"
            f"MemAvailable: {mem_total_kb - mem_used_kb} kB\nCached: 100 kB\n"
        )


class TestCollectors:
    def test_node_cpu_rate(self, cfg, clock):
        states = StatesInformer(clock=clock)
        cache = mc.MetricCache(clock=clock)
        advisor = ma.MetricsAdvisor(states, cache, cfg, clock)
        write_proc(cfg, used_jiffies=1000)
        advisor.collect_once()
        clock.tick(10)
        write_proc(cfg, used_jiffies=1000 + 2000)  # 2000 jiffies = 2 cores * 10s
        advisor.collect_once()
        result = cache.query(mc.NODE_CPU_USAGE, start=0, end=clock.t + 1)
        assert result.latest() == pytest.approx(2.0)
        mem = cache.query(mc.NODE_MEMORY_USAGE, start=0, end=clock.t + 1)
        assert mem.latest() == 400 * 1024

    def test_pod_and_container_usage(self, cfg, clock):
        pod = make_pod(containers=(ContainerMeta("c1", "cid-1"),))
        states = StatesInformer(clock=clock)
        states.set_pods([pod])
        cache = mc.MetricCache(clock=clock)
        advisor = ma.MetricsAdvisor(states, cache, cfg, clock)
        rel = pod.cgroup_dir(cfg)
        crel = cfg.container_cgroup_dir("burstable", pod.uid, "cid-1")
        write_proc(cfg, 100)
        write_cgroup_file(cfg, cg.CPUACCT_USAGE, rel, "0")
        write_cgroup_file(cfg, cg.MEMORY_USAGE, rel, "1048576")
        write_cgroup_file(cfg, cg.CPUACCT_USAGE, crel, "0")
        write_cgroup_file(cfg, cg.MEMORY_USAGE, crel, "524288")
        advisor.collect_once()
        clock.tick(10)
        write_cgroup_file(cfg, cg.CPUACCT_USAGE, rel, str(15 * 10**9))
        write_cgroup_file(cfg, cg.CPUACCT_USAGE, crel, str(5 * 10**9))
        advisor.collect_once()
        pod_cpu = cache.query(mc.POD_CPU_USAGE, {"pod_uid": pod.uid}, 0, clock.t + 1)
        assert pod_cpu.latest() == pytest.approx(1.5)
        c_cpu = cache.query(
            mc.CONTAINER_CPU_USAGE,
            {"pod_uid": pod.uid, "container_id": "cid-1"}, 0, clock.t + 1,
        )
        assert c_cpu.latest() == pytest.approx(0.5)
        pod_mem = cache.query(mc.POD_MEMORY_USAGE, {"pod_uid": pod.uid}, 0, clock.t + 1)
        assert pod_mem.latest() == 1048576

    def test_be_usage_v2(self, tmp_path, clock):
        cfg = make_test_config(tmp_path, use_cgroup_v2=True)
        states = StatesInformer(clock=clock)
        cache = mc.MetricCache(clock=clock)
        advisor = ma.MetricsAdvisor(states, cache, cfg, clock)
        rel = cfg.kube_qos_dir("besteffort")
        write_proc(cfg, 100)
        write_cgroup_file(cfg, cg.CPU_STAT, rel, "usage_usec 0\n")
        advisor.collect_once()
        clock.tick(5)
        write_cgroup_file(cfg, cg.CPU_STAT, rel, f"usage_usec {4 * 10**6 * 5}\n")
        advisor.collect_once()
        be = cache.query(mc.BE_CPU_USAGE, start=0, end=clock.t + 1)
        assert be.latest() == pytest.approx(4.0)

    def test_throttled_ratio(self, cfg, clock):
        pod = make_pod()
        states = StatesInformer(clock=clock)
        states.set_pods([pod])
        cache = mc.MetricCache(clock=clock)
        advisor = ma.MetricsAdvisor(states, cache, cfg, clock)
        rel = pod.cgroup_dir(cfg)
        write_proc(cfg, 100)
        write_cgroup_file(cfg, cg.CPU_STAT, rel, "nr_periods 100\nnr_throttled 10\n")
        advisor.collect_once()
        clock.tick(10)
        write_cgroup_file(cfg, cg.CPU_STAT, rel, "nr_periods 200\nnr_throttled 60\n")
        advisor.collect_once()
        thr = cache.query(mc.CONTAINER_CPU_THROTTLED, {"pod_uid": pod.uid}, 0, clock.t + 1)
        assert thr.latest() == pytest.approx(0.5)

    def test_sys_resource(self, cfg, clock):
        pod = make_pod()
        states = StatesInformer(clock=clock)
        states.set_pods([pod])
        cache = mc.MetricCache(clock=clock)
        cache.append(mc.NODE_CPU_USAGE, 4.0)
        cache.append(mc.POD_CPU_USAGE, 1.5, {"pod_uid": pod.uid})
        cache.append(mc.NODE_MEMORY_USAGE, 1000.0)
        cache.append(mc.POD_MEMORY_USAGE, 400.0, {"pod_uid": pod.uid})
        advisor = ma.MetricsAdvisor(states, cache, cfg, clock)
        ma.SysResourceCollector(advisor.deps).collect()
        assert cache.query(mc.SYS_CPU_USAGE, start=0, end=clock.t + 1).latest() == 2.5
        assert cache.query(mc.SYS_MEMORY_USAGE, start=0, end=clock.t + 1).latest() == 600.0


class TestStatesInformer:
    def test_callbacks_fire(self, clock):
        states = StatesInformer(clock=clock)
        seen = []
        states.register_callback("all-pods", lambda pods: seen.append(len(pods)))
        states.set_pods([make_pod(), make_pod(uid="pod-2")])
        assert seen == [2]

    def test_node_metric_aggregation(self, clock):
        cache = mc.MetricCache(clock=clock)
        states = StatesInformer(metric_cache=cache, clock=clock)
        pod = make_pod(priority=9500)
        states.set_pods([pod])
        states.set_node(NodeInfo(name="n1"))
        for i in range(10):
            cache.append(mc.NODE_CPU_USAGE, 1.0 + i * 0.1, ts=clock.t - 100 + i)
            cache.append(mc.NODE_MEMORY_USAGE, 1e9, ts=clock.t - 100 + i)
            cache.append(mc.POD_CPU_USAGE, 0.5, {"pod_uid": pod.uid},
                         ts=clock.t - 100 + i)
        status = states.build_node_metric(window_seconds=300)
        assert status.node_usage.cpu_milli == pytest.approx(1450, abs=1)
        assert status.aggregated_node_usage is not None
        assert status.aggregated_node_usage.cpu_milli_p[0.5] == 1400
        assert len(status.pods_metrics) == 1
        assert status.pods_metrics[0].usage.cpu_milli == 500
        assert status.pods_metrics[0].qos_class == "LS"


class TestExtensionProtocol:
    def test_qos_label_roundtrip(self):
        from koordinator_tpu.api import extension as ext

        labels = {}
        ext.set_pod_qos(labels, QoSClass.BE)
        assert labels[ext.LABEL_POD_QOS] == "BE"
        assert ext.get_pod_qos(labels) == QoSClass.BE
        assert ext.get_pod_qos({}) == QoSClass.NONE

    def test_resource_status_roundtrip(self):
        from koordinator_tpu.api import extension as ext

        ann = {}
        ext.set_resource_status(ann, "0-3,8")
        assert ext.get_resource_status(ann)["cpuset"] == "0-3,8"

    def test_device_allocation_roundtrip(self):
        from koordinator_tpu.api import extension as ext

        ann = {}
        allocs = {"gpu": [{"minor": 0, "resources": {"kubernetes.io/gpu-core": 50}}]}
        ext.set_device_allocations(ann, allocs)
        assert ext.get_device_allocations(ann) == allocs

    def test_amplification_and_normalization(self):
        from koordinator_tpu.api import extension as ext

        ann = {ext.ANNOTATION_NODE_AMPLIFICATION: '{"cpu": 1.5}',
               ext.ANNOTATION_CPU_NORMALIZATION: "1.2"}
        assert ext.get_node_amplification_ratios(ann) == {"cpu": 150}
        assert ext.get_cpu_normalization_ratio_pct(ann) == 120
        assert ext.get_cpu_normalization_ratio_pct({}) == 100


class TestMetricCachePersistence:
    """Metric-history persistence across agent restart (reference role:
    pkg/koordlet/metriccache/tsdb_storage.go:29 — the embedded TSDB is
    persisted on the node).  Memory-only ring buffers meant a koordlet
    restart zeroed the NodeMetric aggregation windows and suppress/evict
    ran on cold data."""

    def test_snapshot_restore_roundtrip(self, clock, tmp_path):
        path = str(tmp_path / "mc.npz")
        cache = mc.MetricCache(capacity_per_series=32, clock=clock)
        for i in range(40):  # wraps the ring
            cache.append(mc.NODE_CPU_USAGE, float(i), ts=1000.0 + i)
        for i in range(5):
            cache.append(mc.POD_CPU_USAGE, 0.1 * i,
                         labels={"pod_uid": "p1"}, ts=1000.0 + i)
        cache.set_kv("json_ok", {"a": 1})
        cache.set_kv("opaque", object())  # not JSON-serializable: dropped
        cache.snapshot(path)

        fresh = mc.MetricCache(capacity_per_series=32, clock=clock)
        assert fresh.restore(path)
        orig = cache.query(mc.NODE_CPU_USAGE, start=0, end=2000)
        got = fresh.query(mc.NODE_CPU_USAGE, start=0, end=2000)
        assert got.count == orig.count == 32
        assert got.avg() == orig.avg()
        assert got.latest() == orig.latest() == 39.0
        pod = fresh.query(mc.POD_CPU_USAGE, labels={"pod_uid": "p1"},
                          start=0, end=2000)
        assert pod.count == 5
        assert fresh.get_kv("json_ok") == {"a": 1}
        assert fresh.get_kv("opaque") is None
        # appends continue cleanly after restore (head position correct)
        fresh.append(mc.NODE_CPU_USAGE, 99.0, ts=1100.0)
        assert fresh.query(mc.NODE_CPU_USAGE, 
                           start=0, end=2000).latest() == 99.0

    def test_restore_smaller_capacity_keeps_newest(self, clock, tmp_path):
        path = str(tmp_path / "mc.npz")
        cache = mc.MetricCache(capacity_per_series=64, clock=clock)
        for i in range(50):
            cache.append(mc.NODE_CPU_USAGE, float(i), ts=1000.0 + i)
        cache.snapshot(path)
        small = mc.MetricCache(capacity_per_series=16, clock=clock)
        assert small.restore(path)
        got = small.query(mc.NODE_CPU_USAGE, start=0, end=2000)
        assert got.count == 16
        # the NEWEST 16 samples survive, in order
        assert got.latest() == 49.0
        assert got.values.min() == 34.0

    def test_corrupt_snapshot_starts_fresh(self, clock, tmp_path):
        path = str(tmp_path / "mc.npz")
        (tmp_path / "mc.npz").write_bytes(b"not an npz file")
        cache = mc.MetricCache(clock=clock)
        assert not cache.restore(path)
        assert not cache.restore(str(tmp_path / "missing.npz"))
        cache.append(mc.NODE_CPU_USAGE, 1.0)
        assert cache.query(mc.NODE_CPU_USAGE, start=0,
                           end=2000).count == 1

    def test_daemon_restart_unbroken_p95_window(self, clock, cfg):
        """The done-criterion: kill and restart the daemon, and the
        reporter's p95-over-window is computed over the FULL window, not
        the seconds since restart."""
        from koordinator_tpu.koordlet.daemon import Daemon
        from koordinator_tpu.koordlet.statesinformer import NodeInfo

        d1 = Daemon(cfg=cfg, clock=clock)
        # five minutes of 30s node-usage samples (collector cadence)
        for i in range(11):
            d1.metric_cache.append(mc.NODE_CPU_USAGE, 2.0 + 0.1 * i,
                                   ts=clock.t)
            d1.metric_cache.append(mc.NODE_MEMORY_USAGE, 1e9 + i * 1e7,
                                   ts=clock.t)
            clock.tick(30)
        before = d1.states.build_node_metric(window_seconds=300.0)
        # interval snapshot fires on a tick (kill -9 survivability: no
        # stop() needed) — arm the proc files the collectors read
        write_proc(cfg, used_jiffies=1000)
        d1.tick()
        # ... process dies here without stop() ...

        d2 = Daemon(cfg=cfg, clock=clock)
        d2.states.set_node(NodeInfo(name="n0", allocatable={}))
        after = d2.states.build_node_metric(window_seconds=300.0)
        assert after.aggregated_node_usage.duration_seconds == pytest.approx(
            before.aggregated_node_usage.duration_seconds)
        assert after.aggregated_node_usage.duration_seconds >= 250.0
        for q in (0.5, 0.9, 0.95, 0.99):
            assert (after.aggregated_node_usage.cpu_milli_p[q]
                    == before.aggregated_node_usage.cpu_milli_p[q])
            assert (after.aggregated_node_usage.memory_bytes_p[q]
                    == before.aggregated_node_usage.memory_bytes_p[q])
        # and the daemon-level stop() snapshot also persists (SIGTERM)
        d2.metric_cache.append(mc.NODE_CPU_USAGE, 9.0, ts=clock.t)
        d2.stop()
        d3 = Daemon(cfg=cfg, clock=clock)
        assert d3.metric_cache.query(
            mc.NODE_CPU_USAGE, start=0, end=clock.t + 1).latest() == 9.0
