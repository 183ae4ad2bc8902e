"""Native shim tests: batch reader correctness + fallback, CPI counter
degradation, collector integration with pod churn."""

import os

import pytest

from koordinator_tpu import native


@pytest.fixture
def files(tmp_path):
    paths = []
    for i in range(20):
        p = tmp_path / f"f{i}"
        p.write_text(f"content {i}\n")
        paths.append(str(p))
    return paths


def test_library_name_follows_the_source(tmp_path, monkeypatch):
    """The loader only ever opens the library its own source builds: a
    stale or foreign .so in the git-ignored build directory has another
    name and is rebuilt over, never loaded."""
    src = tmp_path / "koordsys.cpp"
    with open(native._SRC, "rb") as f:
        src.write_bytes(f.read())
    monkeypatch.setattr(native, "_SRC", str(src))
    assert native._lib_path() == native._LIB
    src.write_bytes(src.read_bytes() + b"\n// edited\n")
    assert native._lib_path() != native._LIB


class TestBatchReader:
    def test_read_and_missing(self, files, tmp_path):
        reader = native.BatchReader(files + [str(tmp_path / "nope")])
        out = reader.read()
        assert out[0] == "content 0\n"
        assert out[19] == "content 19\n"
        assert out[20] is None

    def test_reread_sees_changes(self, files):
        reader = native.BatchReader(files[:1])
        assert reader.read()[0] == "content 0\n"
        with open(files[0], "w") as f:
            f.write("changed\n")
        assert reader.read()[0] == "changed\n"

    def test_truncation(self, tmp_path):
        p = tmp_path / "big"
        p.write_text("x" * 10000)
        out = native.BatchReader([str(p)], max_bytes=128).read()
        assert out[0] is not None and len(out[0]) <= 127

    def test_empty(self):
        assert native.BatchReader([]).read() == []

    def test_python_fallback_matches(self, files, tmp_path, monkeypatch):
        native_out = native.BatchReader(files + [str(tmp_path / "no")]).read()
        reader = native.BatchReader(files + [str(tmp_path / "no")])
        reader._lib = None  # force fallback
        assert reader.read() == native_out


class TestCPICounter:
    def test_graceful_unavailable(self, tmp_path):
        counter = native.CPICounter(str(tmp_path / "nonexistent"), 4)
        # either perf works (real kernel + perms) or open() returns False;
        # a nonexistent cgroup dir must always be False
        assert counter.open() is False
        assert counter.read() is None
        counter.close()  # no-op, no crash


class TestCollectorChurnRebuild:
    def test_reader_rebuilt_on_pod_set_change(self, tmp_path):
        from koordinator_tpu.api.qos import QoSClass
        from koordinator_tpu.koordlet import metriccache as mc
        from koordinator_tpu.koordlet import metricsadvisor as ma
        from koordinator_tpu.koordlet.statesinformer import PodMeta, StatesInformer
        from koordinator_tpu.koordlet.system import cgroup as cg
        from koordinator_tpu.koordlet.system.config import make_test_config
        from tests.test_koordlet_metrics import FakeClock
        from tests.test_koordlet_system import write_cgroup_file

        cfg = make_test_config(tmp_path)
        clock = FakeClock()
        states = StatesInformer(clock=clock)
        cache = mc.MetricCache(clock=clock)
        collector = ma.PodResourceCollector(ma._Deps(states, cache, cfg, clock))

        def make(uid):
            p = PodMeta(uid=uid, name=uid, namespace="d",
                        qos_class=QoSClass.LS, kube_qos="burstable")
            write_cgroup_file(cfg, cg.CPUACCT_USAGE, p.cgroup_dir(cfg), "0")
            write_cgroup_file(cfg, cg.MEMORY_USAGE, p.cgroup_dir(cfg), "100")
            return p

        states.set_pods([make("a")])
        collector.collect()
        first_key = collector._reader_key
        assert len(first_key) == 2
        states.set_pods([make("a"), make("b")])
        collector.collect()
        assert len(collector._reader_key) == 4
        assert collector._reader_key != first_key
        # memory visible for both
        clock.tick(1)
        collector.collect()
        assert cache.query(mc.POD_MEMORY_USAGE, {"pod_uid": "b"},
                           0, clock.t + 1).latest() == 100.0
