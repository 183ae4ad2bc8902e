"""Device-share scheduling kernels: GPU/RDMA fit, scoring, allocation.

TPU-native equivalent of the reference's deviceshare plugin
(pkg/scheduler/plugins/deviceshare/: device_cache.go nodeDevice state,
device_allocator.go AutopilotAllocator + tryJointAllocate, allocator_gpu.go,
gpu_shared_resource_templates_cache.go partition templates, scoring.go).

Resource model (apis/extension/device_share.go): a device exposes
``core`` in percent-of-device units (100 = one whole device — the reference's
koordinator.sh/gpu-core) and ``memory`` in MiB. A request is either

- **shared**: core < 100 — lands on ONE device with enough free core+memory, or
- **whole**: core = n*100 — takes n fully-free devices (multi-device requests
  cannot split a device, matching ValidateDeviceRequest).

Cluster-wide device state is a (nodes x max-devices x 2) tensor per device
type; Filter/Score are batched over all nodes, allocation picks device ids on
the chosen node (same batched-filter / single-node-reserve split as
ops/numa.py). Joint GPU+NIC allocation prefers devices of both types in one
topology group (device_allocator.go:208 tryJointAllocate).

The GPU type rides the batched solve itself (``ClusterState.devices``, in
the snapshot's node rows): :func:`device_fit_pods` joins feasibility,
:func:`candidate_device_fit` and :func:`grant_rows` the propose/accept
rounds and the exact scan, and the grant comes back beside the assignment
as a :class:`DeviceGrants`.  What the solve does NOT exercise: RDMA and
``joint_allocate``, partition templates, DeviceShare's Score
(``device_score``) and the spread strategy; those stay single-node host
calls of their callers.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

from koordinator_tpu.api.resources import ResourceDim
from koordinator_tpu.ops.select import take_by_rank

#: Per-device resource dims: core (percent, 100 per device) and memory (MiB).
DEV_CORE = 0
DEV_MEM = 1
NUM_DEV_DIMS = 2

#: Scheduler-facing allocate strategies (DeviceShareArgs scoring strategy).
DEV_BINPACK = 0   # most-allocated: fill busy devices/nodes first
DEV_SPREAD = 1    # least-allocated


@struct.dataclass
class DeviceState:
    """One device type (GPU, RDMA, ...) across the cluster, padded (N, D)."""

    total: jax.Array    # (N, D, 2) int32 per-device capacity
    free: jax.Array     # (N, D, 2) int32 unallocated
    valid: jax.Array    # (N, D) bool — device exists
    healthy: jax.Array  # (N, D) bool — Device CRD health
    group: jax.Array    # (N, D) int32 topology group (PCIe/NUMA) for joint alloc

    @property
    def shape(self) -> tuple[int, int]:
        return self.valid.shape

    @classmethod
    def zeros(cls, nodes: int, devices: int = 16) -> "DeviceState":
        return cls(
            total=jnp.zeros((nodes, devices, NUM_DEV_DIMS), jnp.int32),
            free=jnp.zeros((nodes, devices, NUM_DEV_DIMS), jnp.int32),
            valid=jnp.zeros((nodes, devices), bool),
            healthy=jnp.zeros((nodes, devices), bool),
            group=jnp.zeros((nodes, devices), jnp.int32),
        )

    @classmethod
    def build(
        cls,
        per_node_devices: list[list[dict]],
        node_capacity: int | None = None,
        device_capacity: int | None = None,
    ) -> "DeviceState":
        """From host records: one dict per device with keys
        core/memory/group/healthy (Device CRD device_types.go:112 entries)."""
        # at call time: the state module imports this one
        from koordinator_tpu.state.cluster_state import _bucket

        n = len(per_node_devices)
        ncap = node_capacity or _bucket(max(n, 1))
        dmax = max((len(d) for d in per_node_devices), default=1)
        dcap = device_capacity or _bucket(max(dmax, 1), minimum=8)
        total = np.zeros((ncap, dcap, NUM_DEV_DIMS), np.int32)
        valid = np.zeros((ncap, dcap), bool)
        healthy = np.zeros((ncap, dcap), bool)
        group = np.zeros((ncap, dcap), np.int32)
        for i, devs in enumerate(per_node_devices):
            for j, d in enumerate(devs):
                total[i, j, DEV_CORE] = d.get("core", 100)
                total[i, j, DEV_MEM] = d.get("memory", 0)
                valid[i, j] = True
                healthy[i, j] = d.get("healthy", True)
                group[i, j] = d.get("group", 0)
        return cls(
            total=jnp.asarray(total),
            free=jnp.asarray(total.copy()),
            valid=jnp.asarray(valid),
            healthy=jnp.asarray(healthy),
            group=jnp.asarray(group),
        )


def split_request(core: int, memory: int) -> tuple[int, int, int]:
    """(n_whole, per_device_core, per_device_memory) — ValidateDeviceRequest.

    core=350 is invalid in the reference (multi-device must be whole); we
    round it up to 4 whole devices to stay total-capacity-safe.
    """
    if core <= 100:
        return (0, core, memory)
    n = -(-core // 100)
    return (n, 100, -(-memory // n) if memory else 0)


def _usable(dev: DeviceState) -> jnp.ndarray:
    return dev.valid & dev.healthy


def _whole_free(dev: DeviceState) -> jnp.ndarray:
    """(N, D) bool — device is fully unallocated."""
    return _usable(dev) & jnp.all(dev.free == dev.total, axis=-1)


def device_fit(
    dev: DeviceState,
    n_whole: jnp.ndarray,   # () int32, 0 = shared request
    core: jnp.ndarray,      # () per-device core ask
    memory: jnp.ndarray,    # () per-device memory ask
) -> jnp.ndarray:
    """(N,) bool — batched Filter over all nodes."""
    fits_each = (
        _usable(dev)
        & (dev.free[..., DEV_CORE] >= core)
        & (dev.free[..., DEV_MEM] >= memory)
    )
    shared_ok = jnp.any(fits_each, axis=-1)
    # whole devices must also cover the per-device ask (a fully-free device
    # with less memory than asked is not a fit)
    whole_capable = (
        _whole_free(dev)
        & (dev.total[..., DEV_CORE] >= core)
        & (dev.total[..., DEV_MEM] >= memory)
    )
    whole_ok = jnp.sum(whole_capable.astype(jnp.int32), axis=-1) >= n_whole
    return jnp.where(n_whole > 0, whole_ok, shared_ok)


def device_score(
    dev: DeviceState,
    n_whole: jnp.ndarray,
    core: jnp.ndarray,
    memory: jnp.ndarray,
    strategy: int = DEV_BINPACK,
) -> jnp.ndarray:
    """(N,) int32 in [0, 100] — scoring.go's most/least-allocated over the
    node's device pool (utilization after placing the request)."""
    total = jnp.maximum(jnp.sum(jnp.where(dev.valid[..., None], dev.total, 0),
                                axis=1), 1)                    # (N, 2)
    used = total - jnp.sum(jnp.where(dev.valid[..., None], dev.free, 0), axis=1)
    ask_core = jnp.where(n_whole > 0, n_whole * 100, core)
    ask = jnp.stack([ask_core, jnp.where(n_whole > 0, n_whole * memory, memory)])
    util = jnp.clip((used + ask[None, :]) * 100 // total, 0, 100)  # (N, 2)
    score = jnp.sum(util, axis=-1) // NUM_DEV_DIMS
    if strategy == DEV_BINPACK:
        return score.astype(jnp.int32)
    return (100 - score).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("strategy",))
def allocate_on_node(
    dev: DeviceState,
    node: jnp.ndarray,       # () int32 chosen node row
    n_whole: jnp.ndarray,
    core: jnp.ndarray,
    memory: jnp.ndarray,
    strategy: int = DEV_BINPACK,
    prefer_group: jnp.ndarray | None = None,  # () int32, -1 = no preference
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Pick device ids on one node: returns ((D,) bool selection, ok).

    Shared: best-fit — the fitting device with the least free core (binpack)
    or most free (spread). Whole: n fully-free devices, preferring the
    requested topology group, then group-crowding order (keeps big groups
    intact, the allocator's honor-device-topology behavior).
    """
    d = dev.valid.shape[1]
    free = dev.free[node]            # (D, 2)
    total = dev.total[node]
    usable = dev.valid[node] & dev.healthy[node]
    groups = dev.group[node]

    in_group = (
        (groups == prefer_group) & (prefer_group >= 0)
        if prefer_group is not None
        else jnp.zeros(d, bool)
    )

    # -- shared single-device path: best-fit within the preferred topology
    # group first, then any group (same-group-then-fallback, tryJointAllocate)
    fits = usable & (free[:, DEV_CORE] >= core) & (free[:, DEV_MEM] >= memory)
    fit_key = free[:, DEV_CORE] if strategy == DEV_BINPACK else -free[:, DEV_CORE]
    shared_sel, shared_ok = take_by_rank(
        (jnp.arange(d), fit_key, ~in_group, ~fits), fits, jnp.int32(1)
    )

    # -- whole-devices path (per-device capacity must cover the ask)
    wfree = (
        usable
        & jnp.all(free == total, axis=-1)
        & (total[:, DEV_CORE] >= core)
        & (total[:, DEV_MEM] >= memory)
    )
    # group crowding: how many whole-free devices share my group (take from
    # the group that can satisfy the request with least leftover)
    grp_count = jax.ops.segment_sum(
        wfree.astype(jnp.int32), jnp.clip(groups, 0), d
    )[jnp.clip(groups, 0)]
    can_satisfy = grp_count >= n_whole
    whole_sel, whole_ok = take_by_rank(
        (
            jnp.arange(d),
            jnp.where(can_satisfy, grp_count, jnp.iinfo(jnp.int32).max),
            ~in_group,
            ~wfree,
        ),
        wfree,
        n_whole,
    )

    sel = jnp.where(n_whole > 0, whole_sel, shared_sel)
    ok = jnp.where(n_whole > 0, whole_ok, shared_ok)
    return sel & ok, ok


def commit_allocation(
    dev: DeviceState,
    node: jnp.ndarray,
    selection: jnp.ndarray,  # (D,) bool
    core: jnp.ndarray,
    memory: jnp.ndarray,
) -> DeviceState:
    """Subtract the per-device ask from the selected devices' free."""
    ask = jnp.stack([core, memory]).astype(jnp.int32)
    delta = selection[:, None] * ask[None, :]
    return dev.replace(free=dev.free.at[node].add(-delta))


def release_allocation(
    dev: DeviceState,
    node: jnp.ndarray,
    selection: jnp.ndarray,
    core: jnp.ndarray,
    memory: jnp.ndarray,
) -> DeviceState:
    ask = jnp.stack([core, memory]).astype(jnp.int32)
    delta = selection[:, None] * ask[None, :]
    return dev.replace(free=dev.free.at[node].add(delta))


@functools.partial(jax.jit, static_argnames=("strategy", "nic_required"))
def joint_allocate(
    gpu: DeviceState,
    nic: DeviceState,
    node: jnp.ndarray,
    n_whole: jnp.ndarray,
    core: jnp.ndarray,
    memory: jnp.ndarray,
    nic_core: jnp.ndarray,
    nic_memory: jnp.ndarray,
    strategy: int = DEV_BINPACK,
    nic_required: bool = False,
):
    """GPU + NIC co-allocation on one node (tryJointAllocate semantics).

    Allocates GPUs first, then a NIC in the same topology group as the chosen
    GPUs; if no same-group NIC fits, falls back to any NIC (or fails when
    ``nic_required``, the JointAllocate required-scope behavior).

    Returns (gpu_sel, nic_sel, ok).
    """
    gpu_sel, gpu_ok = allocate_on_node(
        gpu, node, n_whole, core, memory, strategy=strategy
    )
    # majority group of the selected gpus (first selected device's group)
    first = jnp.argmax(gpu_sel)
    gpu_group = jnp.where(gpu_ok, gpu.group[node][first], -1)

    nic_sel, nic_ok = allocate_on_node(
        nic, node, jnp.int32(0), nic_core, nic_memory,
        strategy=strategy, prefer_group=gpu_group,
    )
    # required mode: the NIC AND every selected GPU must share one group
    # (a multi-group GPU spread has no single group for the NIC to sit in)
    nic_same_group = jnp.any(nic_sel & (nic.group[node] == gpu_group))
    gpus_one_group = jnp.all(~gpu_sel | (gpu.group[node] == gpu_group))
    if nic_required:
        nic_ok = nic_ok & nic_same_group & gpus_one_group
    ok = gpu_ok & nic_ok
    return gpu_sel & ok, nic_sel & ok, ok


def partition_allocate(
    dev: DeviceState,
    node: jnp.ndarray,
    templates: jnp.ndarray,   # (T, D) bool — allowed whole-device partitions
    n_whole: jnp.ndarray,     # () devices wanted
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Pick a partition-template-conforming whole-device set (GPU partition
    tables, gpu_shared_resource_templates_cache.go): the selection must be an
    exact template row whose devices are all free; earlier rows win (the
    table's preference order)."""
    wfree = _whole_free(dev)[node]                         # (D,)
    sizes = jnp.sum(templates.astype(jnp.int32), axis=-1)  # (T,)
    fits = (
        (sizes == n_whole)
        & jnp.all(~templates | wfree[None, :], axis=-1)
    )
    pick = jnp.argmax(fits)                                # first fitting row
    ok = jnp.any(fits)
    return templates[pick] & ok, ok


# ---------------------------------------------------------------------------
# The device stage of the batched solve
# ---------------------------------------------------------------------------
#
# DeviceShare's Filter and Reserve for the GPU type, over every pod of a
# batch at once.  A pod's device request is read off its request vector
# (``ResourceDim.GPU`` in percent of one device, ``GPU_MEMORY`` in MiB:
# upstream's gpu-core / gpu-memory), split as :func:`split_request` does.
# The grant rule is ``allocate_on_node``'s at its default (``DEV_BINPACK``,
# no preferred group), written over a leading pod axis.

#: a device's minor index occupies the low bits of a grant ranking key
_MINOR_BITS = 6
MAX_DEVICES_PER_NODE = 1 << _MINOR_BITS


@struct.dataclass
class DeviceGrants:
    """What the device stage decided for each pod of a solve."""

    selection: jax.Array   # (P, D) bool — the minors granted on its node
    lost_races: jax.Array  # (P,) int32 — accepts undone for want of a device


@struct.dataclass
class PodDeviceRequests:
    """A batch's device requests, split per device (all (P,))."""

    n_whole: jax.Array   # int32, 0 = shared (one device)
    core: jax.Array      # int32 per-device core ask
    memory: jax.Array    # int32 per-device memory ask
    wants: jax.Array     # bool — the pod asks for a device at all

    @property
    def ask(self) -> jax.Array:
        """(P, 2) per-device amount a grant takes off each chosen device."""
        return jnp.stack([self.core, self.memory], axis=-1)


def pod_device_requests(requests: jnp.ndarray) -> PodDeviceRequests:
    """:func:`split_request` over a (P, R) request tensor."""
    core = requests[..., ResourceDim.GPU]
    memory = requests[..., ResourceDim.GPU_MEMORY]
    n = jnp.where(core > 100, -(-core // 100), 0)
    return PodDeviceRequests(
        n_whole=n.astype(jnp.int32),
        core=jnp.where(n > 0, 100, core).astype(jnp.int32),
        memory=jnp.where(n > 0, -(-memory // jnp.maximum(n, 1)),
                         memory).astype(jnp.int32),
        wants=(core > 0) | (memory > 0),
    )


def _fit(free, total, usable, req: PodDeviceRequests):
    """(P, X) bool: the Filter both callers share, of P pods against X
    device rows each ((P or 1, X, D, 2) / (.., D)); a pod that asks for
    no device fits everywhere."""
    core = req.core[:, None, None]
    memory = req.memory[:, None, None]
    fits_each = (usable & (free[..., DEV_CORE] >= core)
                 & (free[..., DEV_MEM] >= memory))
    whole = (usable & jnp.all(free == total, axis=-1)
             & (total[..., DEV_CORE] >= core)
             & (total[..., DEV_MEM] >= memory))
    shared_ok = jnp.any(fits_each, axis=-1)
    whole_ok = (jnp.sum(whole.astype(jnp.int32), axis=-1)
                >= req.n_whole[:, None])
    ok = jnp.where((req.n_whole > 0)[:, None], whole_ok, shared_ok)
    return ok | ~req.wants[:, None]


@jax.named_scope("deviceshare/fit")
def device_fit_pods(dev: DeviceState, requests: jnp.ndarray,
                    free: jnp.ndarray | None = None) -> jnp.ndarray:
    """(P, N) bool — :func:`device_fit` for every pod of a batch; a pod
    that asks for no device fits everywhere.  ``free`` stands in for
    ``dev.free`` where a solve carries its own."""
    free = dev.free if free is None else free
    return _fit(free[None], dev.total[None], _usable(dev)[None],
                pod_device_requests(requests))


@jax.named_scope("deviceshare/fit")
def candidate_device_fit(dev: DeviceState, free: jnp.ndarray,
                         req: PodDeviceRequests,
                         cand_node: jnp.ndarray) -> jnp.ndarray:
    """(P, k) bool — the same Filter at each pod's candidate nodes only,
    against the free tensor a solve's rounds carry."""
    return _fit(free[cand_node], dev.total[cand_node],
                _usable(dev)[cand_node], req)


@jax.named_scope("deviceshare/grant")
def grant_rows(free, total, usable, group,
               req: PodDeviceRequests) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Reserve for P pods, each on a device row of its own ((P, D, 2) /
    (P, D)): ((P, D) bool selection, (P,) ok).  The rule is
    :func:`allocate_on_node`'s default: shared = the fitting device with
    the least free core, whole = n wholly free devices out of the
    topology group that satisfies the ask with the least left over; ties
    to the lowest minor."""
    d = usable.shape[-1]
    if d > MAX_DEVICES_PER_NODE:
        raise ValueError(f"{d} devices a node exceed the grant key's "
                         f"{MAX_DEVICES_PER_NODE}")
    core, memory = req.core[:, None], req.memory[:, None]
    whole = req.n_whole[:, None] > 0
    fits = usable & (free[..., DEV_CORE] >= core) & (free[..., DEV_MEM] >= memory)
    wfree = (usable & jnp.all(free == total, axis=-1)
             & (total[..., DEV_CORE] >= core) & (total[..., DEV_MEM] >= memory))
    same_group = group[:, :, None] == group[:, None, :]
    grp_count = jnp.sum(same_group & wfree[:, None, :], axis=-1)
    eligible = jnp.where(whole, wfree, fits)
    rank_by = jnp.where(
        whole,
        jnp.where(grp_count >= req.n_whole[:, None], grp_count, d + 1),
        jnp.clip(free[..., DEV_CORE], 0, (1 << 23) - 1))
    key = ((~eligible).astype(jnp.int32) << 30
           | rank_by.astype(jnp.int32) << _MINOR_BITS
           | jnp.arange(d, dtype=jnp.int32)[None, :])
    rank = jnp.sum(key[:, None, :] < key[:, :, None], axis=-1)
    k = jnp.where(req.n_whole > 0, req.n_whole, 1)[:, None]
    selected = (rank < k) & eligible
    ok = (jnp.sum(selected, axis=-1) >= k[:, 0]) & req.wants
    return selected & ok[:, None], ok


def apply_grants(dev: DeviceState, node: jnp.ndarray, keep: jnp.ndarray,
                 selection: jnp.ndarray, ask: jnp.ndarray) -> DeviceState:
    """Take the kept pods' grants off their nodes' devices."""
    take = (keep[:, None] & selection)[:, :, None] * ask[:, None, :]
    return dev.replace(
        free=dev.free.at[jnp.where(keep, node, 0)].add(-take))
