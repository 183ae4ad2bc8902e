"""Device collectors: accelerator (GPU/TPU), RDMA, XPU.

Reference: ``pkg/koordlet/metricsadvisor/devices/{gpu,rdma,xpu}/`` — the GPU
collector reads NVML (utilization, memory, topology) into the metric cache
and publishes device inventory for the Device CRD; the RDMA collector lists
InfiniBand devices from sysfs; the XPU collector reads vendor-dropped device
info JSON files from a directory.

TPU-native redesign: the accelerator collector is provider-based — the
default :class:`SysfsAcceleratorProvider` reads an ``accel`` class directory
of the (relocatable) sysfs root.  Collectors stay pure-host I/O — in
particular the koordlet never asks the JAX runtime for its devices: a chip
belongs to one process, and on the scheduler's host that process is the
scheduler sidecar.  Tests run the collectors against the fake filesystem
like every other collector.
"""

from __future__ import annotations

import dataclasses
import json
import os

from koordinator_tpu.api import crds
from koordinator_tpu.koordlet import metriccache as mc


@dataclasses.dataclass
class AccelSample:
    """One accelerator's live sample."""

    uuid: str
    minor: int
    type: str = "gpu"
    core_usage_pct: float = 0.0
    mem_used_bytes: int = 0
    mem_total_bytes: int = 0
    numa_node: int = -1
    busid: str = ""
    health: bool = True


class SysfsAcceleratorProvider:
    """Reads ``<sys_root>/class/accel/<dev>/`` device dirs: files ``uuid``,
    ``minor``, ``mem_total``, ``mem_used``, ``usage_pct``, ``numa_node``
    (the fake-fs contract for tests; real vendors drop the same layout)."""

    def __init__(self, cfg):
        self.cfg = cfg

    @property
    def root(self) -> str:
        return os.path.join(self.cfg.sys_root, "class", "accel")

    def available(self) -> bool:
        return os.path.isdir(self.root)

    def _read(self, dev: str, name: str, default: str = "0") -> str:
        try:
            with open(os.path.join(self.root, dev, name)) as f:
                return f.read().strip()
        except OSError:
            return default

    def sample(self) -> list[AccelSample]:
        out = []
        for i, dev in enumerate(sorted(os.listdir(self.root))):
            if not os.path.isdir(os.path.join(self.root, dev)):
                continue
            out.append(AccelSample(
                uuid=self._read(dev, "uuid", dev),
                minor=int(self._read(dev, "minor", str(i))),
                type=self._read(dev, "type", "gpu"),
                core_usage_pct=float(self._read(dev, "usage_pct")),
                mem_used_bytes=int(self._read(dev, "mem_used")),
                mem_total_bytes=int(self._read(dev, "mem_total")),
                numa_node=int(self._read(dev, "numa_node", "-1")),
                busid=self._read(dev, "busid", ""),
                health=self._read(dev, "health", "1") == "1",
            ))
        return out


class AcceleratorCollector:
    """devices/gpu parity: per-device utilization + memory samples and
    Device-CRD inventory, gated by the Accelerators feature."""

    name = "accelerator"

    def __init__(self, deps, provider=None):
        self.d = deps
        self.provider = provider or SysfsAcceleratorProvider(deps.cfg)

    def enabled(self) -> bool:
        from koordinator_tpu.features import KOORDLET_GATES

        return KOORDLET_GATES.enabled("Accelerators") and self.provider.available()

    def collect(self) -> None:
        now = self.d.clock()
        for s in self.provider.sample():
            labels = {"minor": str(s.minor), "uuid": s.uuid, "type": s.type}
            self.d.cache.append(
                mc.ACCEL_CORE_USAGE, s.core_usage_pct, labels, ts=now
            )
            self.d.cache.append(
                mc.ACCEL_MEM_USED, float(s.mem_used_bytes), labels, ts=now
            )

    def device_infos(self) -> list[crds.DeviceInfo]:
        """Inventory for the Device CRD reporter (Infos() parity)."""
        return [
            crds.DeviceInfo(
                type=s.type, uuid=s.uuid, minor=s.minor, health=s.health,
                numa_node=s.numa_node, busid=s.busid,
                resources={
                    f"{s.type}-core": 100,
                    f"{s.type}-memory": s.mem_total_bytes,
                },
            )
            for s in self.provider.sample()
        ]


class RdmaCollector:
    """devices/rdma parity: InfiniBand device inventory from
    ``<sys_root>/class/infiniband/<dev>/`` (node_guid, ports/*/state)."""

    name = "rdma"

    def __init__(self, deps):
        self.d = deps

    @property
    def root(self) -> str:
        return os.path.join(self.d.cfg.sys_root, "class", "infiniband")

    def enabled(self) -> bool:
        from koordinator_tpu.features import KOORDLET_GATES

        return KOORDLET_GATES.enabled("RDMADevices") and os.path.isdir(self.root)

    def collect(self) -> None:
        # RDMA has no rate metrics in the reference collector; inventory only
        return None

    def device_infos(self) -> list[crds.DeviceInfo]:
        out = []
        for i, dev in enumerate(sorted(os.listdir(self.root))):
            base = os.path.join(self.root, dev)
            if not os.path.isdir(base):
                continue
            guid = ""
            try:
                with open(os.path.join(base, "node_guid")) as f:
                    guid = f.read().strip()
            except OSError:
                pass
            active = True
            ports = os.path.join(base, "ports")
            if os.path.isdir(ports):
                states = []
                for p in sorted(os.listdir(ports)):
                    try:
                        with open(os.path.join(ports, p, "state")) as f:
                            states.append("ACTIVE" in f.read().upper())
                    except OSError:
                        continue
                active = any(states) if states else True
            out.append(crds.DeviceInfo(
                type="rdma", uuid=guid or dev, minor=i, health=active,
                resources={"rdma": 100},
            ))
        return out


class XpuCollector:
    """devices/xpu parity: vendor-dropped device-info JSON files from
    ``<var_run_root>/xpu-device-infos/`` — one JSON per device with
    vendor/model/uuid/minor/memory/topology fields."""

    name = "xpu"

    def __init__(self, deps):
        self.d = deps

    @property
    def root(self) -> str:
        return os.path.join(self.d.cfg.var_run_root, "xpu-device-infos")

    def enabled(self) -> bool:
        from koordinator_tpu.features import KOORDLET_GATES

        return KOORDLET_GATES.enabled("Accelerators") and os.path.isdir(self.root)

    def collect(self) -> None:
        return None

    def device_infos(self) -> list[crds.DeviceInfo]:
        out = []
        for fn in sorted(os.listdir(self.root)):
            if not fn.endswith(".json"):
                continue
            try:
                with open(os.path.join(self.root, fn)) as f:
                    data = json.load(f)
            except (OSError, ValueError):
                continue
            out.append(crds.DeviceInfo(
                type="xpu",
                uuid=str(data.get("uuid", fn[:-5])),
                minor=int(data.get("minor", len(out))),
                health=bool(data.get("healthy", True)),
                numa_node=int(data.get("numaNode", -1)),
                busid=str(data.get("busID", "")),
                resources={
                    str(k): int(v)
                    for k, v in (data.get("resources") or {}).items()
                },
                labels={
                    "vendor": str(data.get("vendor", "")),
                    "model": str(data.get("model", "")),
                },
            ))
        return out


class HamiVGPUCollector:
    """HamiCoreVGPUMonitor parity: per-pod vGPU utilization samples from
    HAMi-core's shared-region dumps.  HAMi-core (the userspace CUDA
    intercept layer) publishes per-process vGPU core/memory accounting in
    a host-visible region; the reference's monitor samples it into the
    metric cache.  The kernel-portable rebuild reads the JSON mirror
    vendors drop under ``<var_run_root>/hami-vgpu-metrics/`` — one file
    per (device, pod) with uuid/podUID/coreUtilPct/memoryUsedBytes."""

    name = "hami-vgpu"

    def __init__(self, deps):
        self.d = deps

    @property
    def root(self) -> str:
        return os.path.join(self.d.cfg.var_run_root, "hami-vgpu-metrics")

    def enabled(self) -> bool:
        from koordinator_tpu.features import KOORDLET_GATES

        return (KOORDLET_GATES.enabled("HamiCoreVGPUMonitor")
                and os.path.isdir(self.root))

    def collect(self) -> None:
        now = self.d.clock()
        try:
            files = sorted(os.listdir(self.root))
        except OSError:
            return
        for fn in files:
            if not fn.endswith(".json"):
                continue
            try:
                with open(os.path.join(self.root, fn)) as f:
                    data = json.load(f)
            except (OSError, ValueError):
                continue
            labels = {"uuid": str(data.get("uuid", "")),
                      "pod_uid": str(data.get("podUID", ""))}
            self.d.cache.append(
                mc.HAMI_VGPU_CORE_USAGE,
                float(data.get("coreUtilPct", 0.0)), labels=labels, ts=now)
            self.d.cache.append(
                mc.HAMI_VGPU_MEM_USED,
                float(data.get("memoryUsedBytes", 0.0)), labels=labels,
                ts=now)

    def device_infos(self) -> list["crds.DeviceInfo"]:
        return []  # metrics-only: inventory comes from the GPU collector


def device_infos_to_inventory(
    infos: list["crds.DeviceInfo"],
) -> dict[str, list[dict]]:
    """Convert Device-CR DeviceInfo records into the per-type inventory the
    scheduler's DeviceManager registers ({type: [{"core", "memory",
    "group"}]} — deviceshare's nodeDevice build format).  Minor ids index
    the list; gaps pad with zero-capacity entries and unhealthy devices
    contribute zero capacity (deviceshare skips unhealthy devices)."""
    out: dict[str, list[dict]] = {}
    for info in infos:
        # Device CRs are external data: a negative minor would wrap the
        # row index, a huge one would materialize that many pad entries
        if not (0 <= int(info.minor) <= 4096):
            continue
        rows = out.setdefault(info.type, [])
        while len(rows) <= info.minor:
            rows.append({"core": 0, "memory": 0, "group": 0})
        # absent data must not create allocatable capacity: deviceshare
        # derives capacity only from reported resources, so a missing
        # {type}-core defaults to 0 (like memory), not full-capacity
        core = int(info.resources.get(f"{info.type}-core", 0))
        memory = int(info.resources.get(f"{info.type}-memory", 0))
        rows[info.minor] = {
            "core": core if info.health else 0,
            "memory": memory if info.health else 0,
            "group": max(int(info.numa_node), 0),
        }
    return out
