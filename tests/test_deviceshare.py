"""Device-share semantics: shared/whole fit, scoring, joint + partition alloc.

Scenarios mirror pkg/scheduler/plugins/deviceshare tests (plugin_test.go fit
cases, device_allocator_test.go joint allocation, benchmark shape 1024 nodes
x 8 GPUs from plugin_benchmark_test.go:143-145).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from koordinator_tpu.ops.deviceshare import (
    DEV_BINPACK,
    DEV_CORE,
    DEV_SPREAD,
    DeviceState,
    allocate_on_node,
    commit_allocation,
    device_fit,
    device_score,
    joint_allocate,
    partition_allocate,
    split_request,
)
from koordinator_tpu.scheduler.device_manager import DeviceManager


def gpu_node(n_gpus=8, mem=81_920, group_size=4):
    return [
        {"core": 100, "memory": mem, "group": j // group_size}
        for j in range(n_gpus)
    ]


def test_split_request():
    assert split_request(50, 1000) == (0, 50, 1000)
    assert split_request(100, 1000) == (0, 100, 1000)
    assert split_request(200, 2000) == (2, 100, 1000)
    assert split_request(350, 0) == (4, 100, 0)  # rounded up to whole


def test_shared_fit_and_whole_fit():
    dev = DeviceState.build([gpu_node(2), []])
    # shared 50% fits node 0 only
    fit = device_fit(dev, jnp.int32(0), jnp.int32(50), jnp.int32(1000))
    assert bool(fit[0]) and not bool(fit[1])
    # 2 whole fits, 3 whole doesn't
    assert bool(device_fit(dev, jnp.int32(2), jnp.int32(100), jnp.int32(0))[0])
    assert not bool(device_fit(dev, jnp.int32(3), jnp.int32(100), jnp.int32(0))[0])


def test_unhealthy_device_excluded():
    devs = gpu_node(2)
    devs[1]["healthy"] = False
    dev = DeviceState.build([devs])
    assert not bool(device_fit(dev, jnp.int32(2), jnp.int32(100), jnp.int32(0))[0])
    assert bool(device_fit(dev, jnp.int32(1), jnp.int32(100), jnp.int32(0))[0])


def test_partial_device_blocks_whole_allocation():
    dev = DeviceState.build([gpu_node(2)])
    sel, ok = allocate_on_node(
        dev, jnp.int32(0), jnp.int32(0), jnp.int32(30), jnp.int32(100)
    )
    dev2 = commit_allocation(dev, jnp.int32(0), sel, jnp.int32(30), jnp.int32(100))
    # one device now partial: only 1 whole device left
    assert bool(device_fit(dev2, jnp.int32(1), jnp.int32(100), jnp.int32(0))[0])
    assert not bool(device_fit(dev2, jnp.int32(2), jnp.int32(100), jnp.int32(0))[0])


def test_binpack_picks_most_allocated_device():
    dev = DeviceState.build([gpu_node(2)])
    sel0 = jnp.zeros(dev.shape[1], bool).at[0].set(True)
    dev = commit_allocation(dev, jnp.int32(0), sel0, jnp.int32(40), jnp.int32(0))
    sel, ok = allocate_on_node(
        dev, jnp.int32(0), jnp.int32(0), jnp.int32(30), jnp.int32(0),
        strategy=DEV_BINPACK,
    )
    assert bool(ok) and bool(sel[0])  # goes to the already-busy device 0
    sel_spread, _ = allocate_on_node(
        dev, jnp.int32(0), jnp.int32(0), jnp.int32(30), jnp.int32(0),
        strategy=DEV_SPREAD,
    )
    assert bool(sel_spread[1])


def test_score_strategies_orient_correctly():
    dev = DeviceState.build([gpu_node(4), gpu_node(4)])
    sel = jnp.zeros(dev.shape[1], bool).at[0].set(True).at[1].set(True)
    dev = commit_allocation(dev, jnp.int32(0), sel, jnp.int32(100), jnp.int32(81_920))
    s_bin = device_score(dev, jnp.int32(1), jnp.int32(100), jnp.int32(0), DEV_BINPACK)
    s_spr = device_score(dev, jnp.int32(1), jnp.int32(100), jnp.int32(0), DEV_SPREAD)
    assert int(s_bin[0]) > int(s_bin[1])   # binpack prefers busier node 0
    assert int(s_spr[1]) > int(s_spr[0])   # spread prefers empty node 1


def test_whole_allocation_prefers_one_group():
    # 8 gpus in two groups of 4; ask 4 whole => all from one group.
    dev = DeviceState.build([gpu_node(8, group_size=4)])
    sel, ok = allocate_on_node(
        dev, jnp.int32(0), jnp.int32(4), jnp.int32(100), jnp.int32(0)
    )
    assert bool(ok)
    groups = np.asarray(dev.group[0])[np.asarray(sel)]
    assert len(set(groups.tolist())) == 1


def test_joint_allocate_same_group_nic():
    gpu = DeviceState.build([gpu_node(8, group_size=4)])
    nic = DeviceState.build(
        [[{"core": 100, "memory": 0, "group": 0}, {"core": 100, "memory": 0, "group": 1}]]
    )
    gsel, nsel, ok = joint_allocate(
        gpu, nic, jnp.int32(0), jnp.int32(4), jnp.int32(100), jnp.int32(0),
        jnp.int32(50), jnp.int32(0),
    )
    assert bool(ok)
    gpu_group = int(np.asarray(gpu.group[0])[np.asarray(gsel)][0])
    nic_group = int(np.asarray(nic.group[0])[np.asarray(nsel)][0])
    assert gpu_group == nic_group


def test_joint_allocate_required_fails_without_same_group_nic():
    gpu = DeviceState.build([gpu_node(4, group_size=4)])  # all group 0
    nic = DeviceState.build([[{"core": 100, "memory": 0, "group": 7}]])
    _, _, ok = joint_allocate(
        gpu, nic, jnp.int32(0), jnp.int32(2), jnp.int32(100), jnp.int32(0),
        jnp.int32(50), jnp.int32(0), nic_required=True,
    )
    assert not bool(ok)
    _, _, ok2 = joint_allocate(
        gpu, nic, jnp.int32(0), jnp.int32(2), jnp.int32(100), jnp.int32(0),
        jnp.int32(50), jnp.int32(0), nic_required=False,
    )
    assert bool(ok2)


def test_partition_templates():
    dev = DeviceState.build([gpu_node(8, group_size=4)])
    d = dev.shape[1]
    t = np.zeros((3, d), bool)
    t[0, 0:4] = True   # partition A: gpus 0-3
    t[1, 4:8] = True   # partition B: gpus 4-7
    t[2, 0:8] = True   # partition C: all 8
    templates = jnp.asarray(t)
    sel, ok = partition_allocate(dev, jnp.int32(0), templates, jnp.int32(4))
    assert bool(ok)
    np.testing.assert_array_equal(np.asarray(sel), t[0])
    # occupy gpu 1 => partition A infeasible, falls to B
    busy = jnp.zeros(d, bool).at[1].set(True)
    dev2 = commit_allocation(dev, jnp.int32(0), busy, jnp.int32(10), jnp.int32(0))
    sel2, ok2 = partition_allocate(dev2, jnp.int32(0), templates, jnp.int32(4))
    assert bool(ok2)
    np.testing.assert_array_equal(np.asarray(sel2), t[1])
    # no 3-device template exists
    _, ok3 = partition_allocate(dev, jnp.int32(0), templates, jnp.int32(3))
    assert not bool(ok3)


def test_device_manager_allocate_release_annotation():
    mgr = DeviceManager()
    mgr.register("gpu", ["n0", "n1"], [gpu_node(4), gpu_node(4)])
    minors = mgr.allocate("gpu", "n0", "pod-a", core=200, memory=16_384)
    assert minors is not None and len(minors) == 2
    ann = mgr.device_allocated_annotation("n0", "pod-a")
    assert ann["gpu"][0]["resources"]["core"] == 100
    # 2 whole left; a 3-whole ask fails until release
    assert mgr.allocate("gpu", "n0", "pod-b", core=300) is None
    mgr.release("n0", "pod-a")
    assert mgr.allocate("gpu", "n0", "pod-b", core=300) is not None


def test_joint_required_rejects_multi_group_gpu_spread():
    # 8 GPUs wanted from two groups of 4 => GPUs span groups; required-scope
    # joint allocation must fail even though a NIC exists in group 0.
    gpu = DeviceState.build([gpu_node(8, group_size=4)])
    nic = DeviceState.build([[{"core": 100, "memory": 0, "group": 0}]])
    _, _, ok = joint_allocate(
        gpu, nic, jnp.int32(0), jnp.int32(8), jnp.int32(100), jnp.int32(0),
        jnp.int32(50), jnp.int32(0), nic_required=True,
    )
    assert not bool(ok)


def test_two_device_types_with_different_node_orders():
    mgr = DeviceManager()
    mgr.register("gpu", ["n0", "n1"], [gpu_node(4), []])
    mgr.register("rdma", ["n1", "n0"],
                 [[{"core": 100}], [{"core": 100}]])
    assert mgr.allocate("gpu", "n0", "pod-a", core=100) is not None
    assert mgr.allocate("rdma", "n0", "pod-a", core=50) is not None
    assert mgr.allocate("gpu", "n1", "pod-b", core=100) is None  # no gpus on n1


def test_device_reallocate_replaces_not_double_charges():
    mgr = DeviceManager()
    mgr.register("gpu", ["n0"], [gpu_node(4)])
    mgr.allocate("gpu", "n0", "pod-a", core=200)
    mgr.allocate("gpu", "n0", "pod-a", core=200)  # retried bind cycle
    ann = mgr.device_allocated_annotation("n0", "pod-a")
    assert len(ann["gpu"]) == 2                    # not 4
    mgr.release("n0", "pod-a")
    assert mgr.allocate("gpu", "n0", "pod-b", core=400) is not None
    # failed re-allocate restores the old grant
    mgr2 = DeviceManager()
    mgr2.register("gpu", ["n0"], [gpu_node(4)])
    a = mgr2.allocate("gpu", "n0", "pod-a", core=200)
    assert mgr2.allocate("gpu", "n0", "pod-a", core=800) is None
    assert mgr2.device_allocated_annotation("n0", "pod-a")["gpu"][0]["minor"] == a[0]


def test_large_cluster_filter_shape():
    # The reference benchmark shape: 1024 nodes x 8 GPUs.
    dev = DeviceState.build([gpu_node(8)] * 1024)
    fit = jax.jit(device_fit)(dev, jnp.int32(8), jnp.int32(100), jnp.int32(0))
    assert fit.shape[0] >= 1024 and bool(fit[:1024].all())


def test_whole_fit_respects_per_device_capacity():
    # 1000-MiB devices: 2 whole devices at 5000 MiB each must NOT fit
    dev = DeviceState.build([gpu_node(2, mem=1_000)])
    fit = device_fit(dev, jnp.int32(2), jnp.int32(100), jnp.int32(5_000))
    assert not bool(fit[0])
    sel, ok = allocate_on_node(
        dev, jnp.int32(0), jnp.int32(2), jnp.int32(100), jnp.int32(5_000)
    )
    assert not bool(ok)
    # and the same ask within capacity fits
    fit = device_fit(dev, jnp.int32(2), jnp.int32(100), jnp.int32(1_000))
    assert bool(fit[0])


def test_shared_alloc_prefers_topology_group():
    # groups {0,1}; group-0 GPU busy so whole GPUs come from group 1;
    # NICs free in both groups -> joint alloc must pick the group-1 NIC
    gpu = DeviceState.build([gpu_node(8, group_size=4)])
    gpu = commit_allocation(
        gpu, jnp.int32(0),
        jnp.asarray([True] + [False] * (gpu.shape[1] - 1)),
        jnp.int32(10), jnp.int32(0),
    )
    nic = DeviceState.build(
        [[{"core": 100, "memory": 0, "group": 0},
          {"core": 100, "memory": 0, "group": 1}]]
    )
    gpu_sel, nic_sel, ok = joint_allocate(
        gpu, nic, jnp.int32(0), jnp.int32(4), jnp.int32(100), jnp.int32(81_920),
        jnp.int32(25), jnp.int32(0), nic_required=True,
    )
    assert bool(ok)
    assert np.flatnonzero(np.asarray(nic_sel)).tolist() == [1]
    assert (np.flatnonzero(np.asarray(gpu_sel)) >= 4).all()


# -- the batch rounds' Reserve loop in blocks ---------------------------------


def full_width_device_accept(dev, dev_free, dreq, choice, accept, order_pos):
    """The oracle: the device Reserve loop as it was before it granted in
    blocks.  Every trip grants on all P rows, each pod on its node's row
    whether or not it is its turn, and keeps the rows whose turn it is.
    Returns what ``batch_assign._device_accept`` does, the rows as trips x
    P."""
    from koordinator_tpu.ops.deviceshare import grant_rows

    p, n = choice.shape[0], dev_free.shape[0]
    usable = dev.valid & dev.healthy
    ask = dreq.ask

    def body(c):
        waiting, free, selection, granted, rows = c
        seg = jnp.where(waiting, choice, n)
        first = jax.ops.segment_min(
            jnp.where(waiting, order_pos, p), seg, num_segments=n + 1)
        turn = waiting & (order_pos == first[seg])
        row = jnp.where(turn, choice, 0)
        sel, ok = grant_rows(
            free[row], dev.total[row], usable[row], dev.group[row], dreq)
        ok = ok & turn
        sel = sel & ok[:, None]
        free = free.at[row].add(-(sel[:, :, None] * ask[:, None, :]))
        return (waiting & ~turn, free, selection | sel, granted | ok,
                rows + p)

    _, free, selection, granted, rows = jax.lax.while_loop(
        lambda c: jnp.any(c[0]), body,
        (accept & dreq.wants, dev_free, jnp.zeros((p, dev.shape[1]), bool),
         jnp.zeros(p, bool), jnp.int32(0)))
    return free, selection, granted, rows


def random_gpu_cluster(seed: int, nodes: int = 40, pods: int = 64):
    """A seeded cluster of 8-GPU nodes (some with 4), a third of the
    devices partly taken, a few unhealthy, and a batch of shared and
    whole-GPU pods: (state with the device plane, its pod batch)."""
    from koordinator_tpu.api.resources import NUM_RESOURCE_DIMS, ResourceDim
    from koordinator_tpu.state.cluster_state import ClusterState, PodBatch

    rng = np.random.default_rng(seed)
    alloc = np.zeros((nodes, NUM_RESOURCE_DIMS), np.int32)
    alloc[:, ResourceDim.CPU] = 10_000_000
    alloc[:, ResourceDim.MEMORY] = 10_000_000
    alloc[:, ResourceDim.GPU] = 800
    alloc[:, ResourceDim.GPU_MEMORY] = 8 * 81_920
    state = ClusterState.from_arrays(alloc)
    per_node = [gpu_node(int(rng.choice([4, 8]))) for _ in range(nodes)]
    dev = DeviceState.build(per_node, node_capacity=state.capacity,
                            device_capacity=8)
    taken = rng.choice([0, 0, 10, 25, 50, 100], size=dev.valid.shape)
    used = np.stack([taken, taken * 819], axis=-1).astype(np.int32)
    free = np.asarray(dev.free) - np.where(
        np.asarray(dev.valid)[..., None], used, 0)
    healthy = np.asarray(dev.healthy) & (rng.random(dev.valid.shape) < 0.95)
    dev = dev.replace(free=jnp.asarray(free), healthy=jnp.asarray(healthy))
    request = np.zeros((pods, NUM_RESOURCE_DIMS), np.int32)
    request[:, ResourceDim.CPU] = 1_000
    request[:, ResourceDim.MEMORY] = 1_024
    share = rng.choice([10, 20, 25, 50], pods)
    whole = rng.choice([1, 2, 4], pods)
    is_share = rng.random(pods) < 0.5
    request[:, ResourceDim.GPU] = np.where(is_share, share, 100 * whole)
    request[:, ResourceDim.GPU_MEMORY] = np.where(
        is_share, share * 819, 81_920 * whole)
    batch = PodBatch.build(request, capacity=pods,
                           priority=rng.integers(0, 100, pods))
    return state.replace(devices=dev), batch


@pytest.mark.parametrize("seed", [21, 22, 23])
def test_the_blocked_device_reserve_loop_grants_what_the_full_width_one_did(
        seed, monkeypatch):
    """64 accepted device proposals on 40 nodes, up to a dozen on one
    node, granted 8 pods a trip, leave the free tensor, the selections
    and the granted set the loop over all 64 rows a trip left; and a
    whole solve in blocks of 8 gives that loop's solve's assignments,
    grants and lost races."""
    from koordinator_tpu.ops import batch_assign as ba
    from koordinator_tpu.ops.assignment import ScoringConfig
    from koordinator_tpu.ops.deviceshare import pod_device_requests

    state, pods = random_gpu_cluster(seed)
    rng = np.random.default_rng(seed + 200)
    dreq = pod_device_requests(pods.requests)
    # half the pods crowd onto 5 nodes, the rest spread over all 40
    choice = jnp.asarray(np.where(rng.random(64) < 0.5,
                                  rng.integers(0, 5, 64),
                                  rng.integers(0, 40, 64)), jnp.int32)
    accept = jnp.asarray(rng.random(64) < 0.9)
    order_pos = jnp.asarray(rng.permutation(64), jnp.int32)
    dev = state.devices
    args = (dev, dev.free, dreq, choice, accept, order_pos)
    got = jax.jit(functools.partial(ba._device_accept, width=8))(*args)
    want = jax.jit(full_width_device_accept)(*args)
    for name, g, w in zip(("free", "selection", "granted"), got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w), name)
    assert np.asarray(want[2]).any() and not np.asarray(want[2]).all()
    assert int(got[3]) % 8 == 0 and int(got[3]) < int(want[3])

    def solve():
        # a fresh function each call: jit reuses a trace of the same one,
        # and the patches below must be traced
        return jax.jit(lambda *a: ba.batch_assign(*a, with_grants=True))(
            state, pods, ScoringConfig.default())

    monkeypatch.setattr(ba, "ACCEPT_BLOCK", 8)
    a, new_state, _, grants = solve()
    monkeypatch.setattr(
        ba, "_device_accept",
        lambda *args: full_width_device_accept(*args[:6]))
    a0, state0, _, grants0 = solve()
    assert int(grants.accept_rows) < int(grants0.accept_rows)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(a0))
    assert (np.asarray(a) >= 0).sum() > 8
    np.testing.assert_array_equal(np.asarray(new_state.devices.free),
                                  np.asarray(state0.devices.free))
    for name in ("selection", "lost_races"):
        np.testing.assert_array_equal(
            np.asarray(getattr(grants.devices, name)),
            np.asarray(getattr(grants0.devices, name)), name)
