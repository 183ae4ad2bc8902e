"""Micro-benchmarks mirroring the reference's ``go test -bench`` harnesses.

The reference ships benchmark harnesses without recorded results
(BASELINE.md); its baseline procedure is "run the reference's harnesses
on our hardware".  This is the TPU-native rebuild of each scenario at
the reference's shapes — where the reference benches one plugin call on
one node, the rebuilt kernel is *batched over every node*, so the honest
comparison unit here is whole-cluster rounds/sec alongside the derived
per-node-call time.

Scenarios (reference file:line):
- numa_filter:       nodenumaresource/plugin_benchmark_test.go:79,190
                     (Filter_CPUBind + PreFilter_LargeCluster)
- numa_take_cpus:    nodenumaresource/cpu_accumulator_test.go:655,706
- deviceshare_filter: deviceshare/plugin_benchmark_test.go:143-145
                     (1024 nodes x 8 GPUs)
- reservation_fit:   reservation/plugin_benchmark_test.go:37 +
                     transformer_benchmark_test.go:42 (restore+fit)
- diagnosis_dump:    frameworkext/schedule_diagnosis_test.go:230,331
- webhook_profile:   webhook/pod/mutating/cluster_colocation_profile_
                     test.go:1868 (profile matching + mutation)

Prints ONE JSON line {"metric": "micro", ...scenario fields...}, stamped
with the platform.  The platform must be a TPU, and a scenario that raises
fails the run.  Device kernels use bench.py's chained-loop methodology; the
two host-path scenarios (diagnosis, webhook) are plain wall clock.
"""

from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import K_ITERS, _median_readback_seconds, require_tpu

N_NODES = 1_024


def _time_kernel(fn, args, iters: int = K_ITERS, n: int = 3) -> float:
    """Seconds per iteration of a scalar-returning jitted chained loop.

    The accumulator feeds back into each call as ``salt``; scenario
    bodies must mix ``salt & 1`` (a genuinely data-dependent 0/1) into
    their inputs — ``& 0`` would constant-fold and let XLA hoist the
    kernel out of the loop, timing one execution instead of ``iters``.
    """

    def chained(*a):
        def body(i, acc):
            return acc + fn(*a, salt=acc)

        return jax.lax.fori_loop(0, iters, body, jnp.int32(0))

    def rtt_fn(*a):
        return a[0].ravel()[0].astype(jnp.int32) * 0

    rtt, _ = _median_readback_seconds(jax.jit(rtt_fn), args, n=n)
    total, _ = _median_readback_seconds(jax.jit(chained), args, n=n)
    return max((total - rtt) / iters, 1e-9)


def bench_numa_filter() -> dict:
    """Batched cpuset Filter over 1,024 nodes x 128 cpus (the LargeCluster
    variant; the reference filters one node per call)."""
    from koordinator_tpu.ops.numa import CPUTopology, cpuset_fit_batched

    topo = CPUTopology.uniform(sockets=2, numa_per_socket=2,
                               cores_per_numa=16, threads_per_core=2)
    topos = jax.tree.map(
        lambda x: jnp.broadcast_to(x, (N_NODES,) + x.shape), topo)
    rng = np.random.default_rng(3)
    refs = jnp.asarray(
        rng.integers(0, 2, (N_NODES, topo.capacity)).astype(np.int32))
    max_ref = jnp.ones(N_NODES, jnp.int32)

    def fn(refs, salt):
        fits = cpuset_fit_batched(
            topos, refs + (salt & 1), max_ref, jnp.int32(16),
            full_pcpus=True)
        return fits.sum().astype(jnp.int32)

    per = _time_kernel(fn, (refs,))
    return {
        "numa_filter_rounds_per_sec_1024n": round(1 / per, 1),
        "numa_filter_ns_per_node_call": round(per / N_NODES * 1e9, 1),
    }


def bench_numa_take_cpus() -> dict:
    """cpuset accumulator take on one 128-cpu node (FullPCPUs,
    most-allocated — cpu_accumulator_test.go's hot case)."""
    from koordinator_tpu.ops.numa import (
        BIND_FULL_PCPUS,
        STRATEGY_MOST_ALLOCATED,
        CPUTopology,
        take_cpus,
    )

    topo = CPUTopology.uniform(sockets=2, numa_per_socket=2,
                               cores_per_numa=16, threads_per_core=2)
    rng = np.random.default_rng(4)
    refs = jnp.asarray(rng.integers(0, 2, topo.capacity).astype(np.int32))

    def fn(refs, salt):
        sel, ok = take_cpus(topo, refs + (salt & 1), jnp.int32(1),
                            jnp.int32(16), bind_policy=BIND_FULL_PCPUS,
                            strategy=STRATEGY_MOST_ALLOCATED)
        return sel.sum().astype(jnp.int32) + ok.astype(jnp.int32)

    per = _time_kernel(fn, (refs,))
    return {"numa_take_cpus_us_per_call_128c": round(per * 1e6, 1)}


def bench_deviceshare_filter() -> dict:
    """Device Filter+Score over 1,024 nodes x 8 GPUs (plugin_benchmark_
    test.go:143's LargeCluster shape, batched instead of per-node)."""
    from koordinator_tpu.ops.deviceshare import (
        DeviceState,
        device_fit,
        device_score,
    )

    dev = DeviceState.build(
        [[{"core": 100, "memory": 80 << 10} for _ in range(8)]
         for _ in range(N_NODES)])
    rng = np.random.default_rng(5)
    used = (np.asarray(dev.total)
            * rng.integers(0, 2, dev.total.shape)).astype(np.int32)
    free = jnp.asarray(np.asarray(dev.total) - used)

    def fn(free, salt):
        d = dev.replace(free=free + (salt & 1))
        fits = device_fit(d, jnp.int32(2), jnp.int32(100),
                          jnp.int32(40 << 10))
        score = device_score(d, jnp.int32(2), jnp.int32(100),
                             jnp.int32(40 << 10))
        return fits.sum().astype(jnp.int32) + (score.sum() & 1)

    per = _time_kernel(fn, (free,))
    return {
        "deviceshare_filter_score_rounds_per_sec_1024n_8gpu": round(
            1 / per, 1),
        "deviceshare_ns_per_node_call": round(per / N_NODES * 1e9, 1),
    }


def bench_reservation_fit() -> dict:
    """Restore+fit: 1,000 pods x 512 reservations over 1,024 nodes
    (transformer_benchmark_test.go restores per node; here one batched
    matrix does every (pod, reservation) pair)."""
    from koordinator_tpu.api.resources import NUM_RESOURCE_DIMS
    from koordinator_tpu.ops.reservation import (
        ReservationSet,
        reservation_fit,
    )

    rng = np.random.default_rng(6)
    r = NUM_RESOURCE_DIMS
    n_rsv, n_pods = 512, 1_000
    reserved = np.zeros((n_rsv, r), np.int32)
    reserved[:, 0] = rng.integers(1_000, 8_000, n_rsv)
    reserved[:, 1] = rng.integers(1_024, 16_384, n_rsv)
    rsv = ReservationSet.build(
        reserved, rng.integers(0, N_NODES, n_rsv).astype(np.int32))
    node_free = jnp.asarray(
        rng.integers(0, 16_000, (N_NODES, r)).astype(np.int32))
    requests = np.zeros((n_pods, r), np.int32)
    requests[:, 0] = rng.integers(500, 4_000, n_pods)
    requests = jnp.asarray(requests)
    match = jnp.asarray(rng.random((n_pods, rsv.capacity)) < 0.25)

    def fn(node_free, salt):
        fits = reservation_fit(rsv, node_free + (salt & 1), requests, match)
        return fits.sum().astype(jnp.int32)

    per = _time_kernel(fn, (node_free,))
    return {
        "reservation_fit_rounds_per_sec_1000p_512v": round(1 / per, 1),
        "reservation_fit_ns_per_pod": round(per / n_pods * 1e9, 1),
    }


def bench_diagnosis_dump() -> dict:
    """Failure-reason dump for 512 unschedulable pods over 10,240 nodes
    (schedule_diagnosis_test.go:230 serializes per-pod diagnoses)."""
    from __graft_entry__ import _build_problem
    from koordinator_tpu.scheduler.diagnosis import explain_pod

    state, pods, cfg = _build_problem(10_240, 512, seed=10)
    explain_pod(state, pods, cfg, 0)  # warm the jitted pieces
    t0 = time.perf_counter()
    msgs = [explain_pod(state, pods, cfg, i).message() for i in range(512)]
    dt = time.perf_counter() - t0
    assert all(msgs)
    return {"diagnosis_dump_pods_per_sec_10240n": round(512 / dt, 1)}


def bench_webhook_profile() -> dict:
    """Profile matching + mutation: 64 selective profiles x 2,000 pods
    (cluster_colocation_profile_test.go:1868 benches one admission)."""
    from koordinator_tpu.api import crds
    from koordinator_tpu.manager.webhook import PodMutatingWebhook

    profiles = [
        crds.ClusterColocationProfile(
            name=f"p{i}", pod_selector={"tier": f"t{i}"}, qos_class="BE",
            koordinator_priority=5000 + i)
        for i in range(64)
    ]
    hook = PodMutatingWebhook(profiles)
    pods = [
        {"metadata": {"name": f"pod-{j}", "namespace": "default",
                      "labels": {"tier": f"t{j % 96}"}},
         "spec": {"containers": [{"name": "m", "resources": {
             "requests": {"cpu": "500m", "memory": "1Gi"}}}]}}
        for j in range(2_000)
    ]
    import copy

    from koordinator_tpu.api import extension as ext

    hook.mutate(copy.deepcopy(pods[0]))  # warm without touching pods[0]
    t0 = time.perf_counter()
    for p in pods:
        hook.mutate(p)
    dt = time.perf_counter() - t0
    matched = sum(
        1 for p in pods
        if ext.LABEL_POD_QOS in p["metadata"].get("labels", {}))
    assert matched  # 2/3 of pods hit a profile
    return {"webhook_admissions_per_sec_64profiles": round(2_000 / dt, 1)}


def main() -> None:
    from koordinator_tpu.compile_cache import enable_compile_cache

    enable_compile_cache()
    out: dict = {"metric": "micro", **require_tpu()}
    for fn in (bench_numa_filter, bench_numa_take_cpus,
               bench_deviceshare_filter, bench_reservation_fit,
               bench_diagnosis_dump, bench_webhook_profile):
        out.update(fn())
    print(json.dumps(out))


if __name__ == "__main__":
    main()
