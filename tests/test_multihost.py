"""Multi-host distributed solve: two real processes under jax.distributed
jointly form one ("pods", "nodes") mesh (the DCN path, SURVEY §2.11 —
"across hosts DCN via jax.distributed") and run the sharded batch solve;
every host must reach the same assignments as a single-process solve.

Each worker gets 4 virtual CPU devices (xla_force_host_platform_device
_count), so the 2-process global mesh has 8 — the same mesh shape the
single-process parity tests (test_mesh.py) use.
"""

import os
import socket
import subprocess
import sys
import textwrap

import numpy as np
import pytest

WORKER = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    coordinator, pid = sys.argv[1], int(sys.argv[2])
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=2, process_id=pid)
    assert jax.device_count() == 8 and jax.local_device_count() == 4

    import numpy as np
    from jax.experimental import multihost_utils
    from koordinator_tpu.api.resources import NUM_RESOURCE_DIMS, ResourceDim
    from koordinator_tpu.ops.assignment import ScoringConfig
    from koordinator_tpu.ops.batch_assign import batch_assign
    from koordinator_tpu.parallel.mesh import (
        shard_cluster_state, shard_pod_batch, solver_mesh)
    from koordinator_tpu.state.cluster_state import ClusterState, PodBatch

    R = NUM_RESOURCE_DIMS
    rng = np.random.default_rng(42)       # identical data on both hosts
    n_nodes, n_pods = 256, 512
    alloc = np.zeros((n_nodes, R), np.int32)
    alloc[:, ResourceDim.CPU] = rng.integers(8_000, 64_000, n_nodes)
    alloc[:, ResourceDim.MEMORY] = rng.integers(16_384, 262_144, n_nodes)
    usage = (alloc * rng.random((n_nodes, R)) * 0.4).astype(np.int32)
    state = ClusterState.from_arrays(alloc, usage=usage, capacity=n_nodes)
    req = np.zeros((n_pods, R), np.int32)
    req[:, ResourceDim.CPU] = rng.integers(100, 2_000, n_pods)
    req[:, ResourceDim.MEMORY] = rng.integers(128, 4_096, n_pods)
    pods = PodBatch.build(
        req, priority=rng.integers(3000, 9999, n_pods).astype(np.int32),
        node_capacity=n_nodes, capacity=n_pods)
    cfg = ScoringConfig.default()

    # single-device reference on host-local data
    ref, _, _ = batch_assign(state, pods, cfg)
    ref = np.asarray(ref)

    # the distributed solve: global mesh across both processes
    mesh = solver_mesh(pods_axis=2)
    assert mesh.devices.size == 8
    gstate = shard_cluster_state(state, mesh)
    gpods = shard_pod_batch(pods, mesh)
    with mesh:
        out, _, _ = batch_assign(gstate, gpods, cfg)
    got = np.asarray(multihost_utils.process_allgather(out, tiled=True))

    np.testing.assert_array_equal(got, ref)
    print(f"OK process {pid}: {int((got >= 0).sum())} assigned")
""")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_two_process_distributed_solve_matches_single(tmp_path):
    script = tmp_path / "worker.py"
    script.write_text(WORKER)
    coordinator = f"127.0.0.1:{_free_port()}"
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    procs = [
        subprocess.Popen(
            [sys.executable, str(script), coordinator, str(pid)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=env, cwd=repo_root)
        for pid in range(2)
    ]
    outs = []
    for proc in procs:
        try:
            out, _ = proc.communicate(timeout=420)
        except subprocess.TimeoutExpired:
            for p in procs:
                p.kill()
            pytest.fail("distributed workers timed out")
        outs.append(out)
    for pid, (proc, out) in enumerate(zip(procs, outs)):
        assert proc.returncode == 0, f"worker {pid} failed:\n{out[-3000:]}"
        assert f"OK process {pid}" in out
