"""Read-only smoke of the L0 host layer against the LIVE kernel.

The fake-fs tests (make_test_config temp trees, the FileTestUtil
equivalent of util_test_tool.go:93) prove the parsers; they cannot catch
path-format drift between our path builders and a real /proc //sys —
that is what this opt-in suite does.  Strictly
read-only: no cgroup writes, no resctrl group creation.

Run with:  pytest -m hostfs tests/test_hostfs_smoke.py
(deselected by default via pytest.ini addopts).
"""

import os

import pytest

from koordinator_tpu import native
from koordinator_tpu.koordlet.system import cgroup as cg
from koordinator_tpu.koordlet.system import procfs, psi
from koordinator_tpu.koordlet.system.config import SystemConfig

pytestmark = [
    pytest.mark.hostfs,
    pytest.mark.skipif(not os.path.exists("/proc/stat"),
                       reason="needs a live Linux procfs"),
]

#: defaults point at the real roots (/proc, /sys/fs/cgroup, /sys)
LIVE = SystemConfig(use_cgroup_v2=os.path.exists(
    "/sys/fs/cgroup/cgroup.controllers"))


def test_native_batch_read_live_proc():
    """ks_batch_read (native/koordsys.cpp) against real /proc: content
    parity with the pure-Python fallback, None for a missing path."""
    assert native.ensure_built() and native.available(), \
        "native shim must build on this box"
    reader = native.BatchReader(
        ["/proc/stat", "/proc/meminfo", "/proc/koord_definitely_missing"],
        max_bytes=65536)
    got = reader.read()
    assert got[0] is not None and got[0].startswith("cpu")
    assert got[1] is not None and "MemTotal" in got[1]
    assert got[2] is None
    py = reader._read_python()
    # /proc/stat jiffies advance between reads; compare structure only
    assert py[0].splitlines()[0].split()[0] == "cpu"
    assert ("MemTotal" in py[1]) and py[2] is None


def test_procfs_parsers_live():
    st = procfs.read_cpu_stat(LIVE)
    assert st.total_jiffies > 0
    assert 0 < st.used_jiffies <= st.total_jiffies
    mi = procfs.read_meminfo(LIVE)
    assert mi.total > (1 << 28)           # >256 MiB of RAM
    assert 0 < mi.used_no_cache <= mi.total
    disks = procfs.read_diskstats(LIVE)
    assert isinstance(disks, dict)        # may be empty in a container


def test_cgroup_path_resolution_live():
    """The v1/v2 filename tables must resolve to files that actually
    exist on the live hierarchy (path-format drift is exactly what the
    temp-tree tests cannot see)."""
    probes = [(cg.CPU_STAT, ""), (cg.CPU_CFS_PERIOD, ""),
              (cg.CPUSET_CPUS, "")]
    resolved = 0
    for res, rel in probes:
        if not res.supported(cg.CgroupVersion.V2 if LIVE.use_cgroup_v2
                             else cg.CgroupVersion.V1):
            continue
        path = cg.resource_path(res, rel, LIVE)
        if os.path.exists(path):
            resolved += 1
            content = cg.cgroup_read(res, rel, LIVE)
            assert content.strip(), path
    assert resolved >= 2, (
        "fewer than 2 of the probe cgroup files resolved — path drift "
        f"against {LIVE.cgroup_root}")
    stat = cg.parse_stat(cg.cgroup_read(cg.CPU_STAT, "", LIVE))
    assert stat, "root cpu.stat parsed to nothing"


def test_psi_live():
    if not os.path.exists("/proc/pressure/cpu"):
        pytest.skip("kernel without PSI")
    with open("/proc/pressure/cpu") as f:
        stats = psi.parse_psi(f.read())
    assert stats.some.total_us >= 0
    assert 0.0 <= stats.some.avg10 <= 100.0
    # cgroup-level PSI: must not raise either way (v1 roots have no
    # pressure files -> empty stats; v2 -> parsed stats)
    by_res = psi.read_psi("", LIVE)
    assert by_res.cpu.some.avg10 >= 0.0
