"""Benchmark: full batched solve + Filter/Score at the north-star shape.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "extra"}.

Shape and target from BASELINE.json: 50k pending pods scheduled against
10,240 nodes; the north-star is the full SOLVE (not just scoring) of 50k pods
in <200ms p99 on a v5e-4 => 250k pods/sec (we run on ONE chip).  The headline
metric times ``batch_assign`` end to end — filter, score, top-k candidate
selection and the propose/accept conflict-resolution rounds with capacity
feedback.  The Filter+Score-only number and the other BASELINE.json configs
(quota @5k pods, gang @10k pods, LowNodeLoad @10k nodes, the spark
colocation loop, deltasync over sockets) ride in ``extra``.

One process, one chip: every phase, the extra configs included, runs in the
process that holds the device.  The run fails — non-zero exit, no record —
when JAX's platform is not a TPU or when any phase raises; a number that
was not measured on the device is never printed.

Timing methodology: each kernel runs K iterations inside one jitted
``fori_loop`` (chained through a data dependency so XLA cannot collapse
them), reduced to a scalar whose host readback ends the timed region; the
host round-trip floor (dispatch + scalar readback of a trivial kernel) is
measured separately and subtracted before dividing by K.
"""

from __future__ import annotations

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

N_NODES = 10_240
N_PODS = 50_000
K_ITERS = 8
BASELINE_PODS_PER_SEC = 250_000.0


def _git_head() -> dict:
    """{"commit": sha, "dirty": bool} of the checkout this bench lives in,
    stamped into every record; an empty stamp where there is no git or no
    repository (the chip copy is a plain directory)."""
    import subprocess

    cwd = os.path.dirname(os.path.abspath(__file__))
    try:
        sha = subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            cwd=cwd, timeout=10).stdout.strip()
        dirty = bool(subprocess.run(
            ["git", "status", "--porcelain"], capture_output=True,
            text=True, cwd=cwd, timeout=10).stdout.strip())
    except (OSError, subprocess.SubprocessError):
        return {"commit": "", "dirty": False}
    return {"commit": sha, "dirty": dirty}


def require_tpu(allow_cpu: bool = False) -> dict:
    """The device stamp every record carries — and the gate: a bench that
    finds no TPU exits non-zero instead of timing another backend, unless
    the caller's user asked for the CPU explicitly (a ``--smoke`` run)."""
    devices = jax.devices()
    stamp = {"platform": devices[0].platform,
             "device_kind": devices[0].device_kind,
             "n_devices": len(devices)}
    if stamp["platform"] != "tpu" and not allow_cpu:
        raise SystemExit(
            f"{os.path.basename(sys.argv[0])}: JAX platform is "
            f"{stamp['platform']!r}, not 'tpu'; refusing to time it")
    return stamp


def _median_readback_seconds(fn, args, n: int = 5):
    """(median_seconds, value) — the warm-up call's value rides along so
    callers can read the chained loop's accumulator without recompiling."""
    value = float(fn(*args))  # compile + warm
    times = []
    for _ in range(n):
        t0 = time.perf_counter()
        float(fn(*args))
        times.append(time.perf_counter() - t0)
    return float(np.median(times)), value


def _chained_loop(assign_fn, iters: int = K_ITERS):
    """The shared chained-iteration scaffold: re-run ``assign_fn(st, pods)``
    ``iters`` times with a data dependency through node_usage so XLA cannot
    dedupe or elide iterations.  The accumulator counts assigned pods per
    iteration (for solve fns; a scalar-returning fn contributes 0/1), so the
    readback doubles as the solve-quality measurement.

    ``pods`` is a TRACED argument, not a closure capture: closed-over pod
    batches become multi-MB HLO constants, and XLA then constant-folds
    pod-dependent work (e.g. the candidate lexsort) at COMPILE time —
    minutes of compile and a solve that silently excludes that work.
    Pod tensors stay loop-invariant, so XLA may still hoist pod-only
    preamble out of the chain; the single-shot latency percentiles
    (solve_latency_ms_p*) include it, the chained mean does not."""

    def fn(st0, pods):
        def body(i, carry):
            acc, usage = carry
            st = st0.replace(node_usage=usage)
            assignments, new_state = assign_fn(st, pods)
            return (acc + (assignments >= 0).sum().astype(jnp.int32),
                    usage + (new_state.node_requested & 1))

        acc, _ = jax.lax.fori_loop(
            0, iters, body, (jnp.int32(0), st0.node_usage))
        return acc

    return fn


def _time_assign(state, pods, assign_fn, rtt: float, n: int = 3,
                 iters: int = K_ITERS):
    """(seconds_per_iter, mean_value_per_iter)."""
    total, value = _median_readback_seconds(
        jax.jit(_chained_loop(assign_fn, iters)), (state, pods), n=n)
    return max((total - rtt) / iters, 1e-9), value / iters


def _bench_quota(rtt: float) -> dict:
    """ElasticQuota LP @ 5k pods x 1,024 nodes, 64-leaf quota tree with
    BINDING constraints: bounded max (checked dims) and contended runtime
    (total min demand ~2x cluster CPU) so admission actually rejects."""
    from __graft_entry__ import _build_problem
    from koordinator_tpu.api.resources import NUM_RESOURCE_DIMS
    from koordinator_tpu.quota.admission import QuotaDeviceState
    from koordinator_tpu.quota.tree import QuotaTree

    rng = np.random.default_rng(7)
    r = NUM_RESOURCE_DIMS
    state, pods, cfg = _build_problem(1_024, 5_000, seed=7)
    total = np.sum(np.asarray(state.node_allocatable), axis=0, dtype=np.int64)
    tree = QuotaTree(total_resource=total)
    for q in range(64):
        mn = np.zeros(r, np.int64)
        mn[0] = int(total[0]) // 128          # mins sum to half the cluster
        mx = np.maximum(total // 16, 1)       # bounded => checked dims
        tree.add(f"q{q}", min=mn, max=mx)
        tree.set_request(f"q{q}", np.maximum(total // 32, 1))  # contended
    tree.refresh_runtime()
    quota, _ = QuotaDeviceState.from_tree(tree)
    qpods = pods.replace(quota_id=jnp.asarray(
        rng.integers(0, 64, pods.capacity), jnp.int32))

    from koordinator_tpu.ops.batch_assign import batch_assign

    per, count = _time_assign(
        state, qpods,
        lambda st, p: batch_assign(st, p, cfg, quota=quota)[:2],
        rtt)
    return {"quota_solve_pods_per_sec_5000p_1024n_64q": round(5_000 / per, 1),
            "quota_solve_assigned_per_round": round(count, 1)}


def _bench_gang(rtt: float) -> dict:
    """Gang ILP @ 10k pods x 1,024 nodes, 256 gangs of ~16, 2 passes."""
    from __graft_entry__ import _build_problem
    from koordinator_tpu.ops.gang import GangInfo, gang_assign

    rng = np.random.default_rng(8)
    state, pods, cfg = _build_problem(1_024, 10_000, seed=8)
    gangs = GangInfo.build(np.full(256, 16, np.int32))
    gpods = pods.replace(gang_id=jnp.asarray(
        rng.integers(-1, 256, pods.capacity), jnp.int32))

    per, count = _time_assign(
        state, gpods,
        lambda st, p: gang_assign(st, p, cfg, gangs, passes=2,
                                  solver="batch")[:2],
        rtt)
    return {"gang_solve_pods_per_sec_10000p_1024n_256g_batch": round(
        10_000 / per, 1),
            "gang_solve_assigned_per_round": round(count, 1)}


def _bench_lownodeload(rtt: float) -> dict:
    """LowNodeLoad hot-migrate @ 10,240 nodes, 20k bound pods."""
    from koordinator_tpu.api.resources import NUM_RESOURCE_DIMS
    from koordinator_tpu.descheduler.lownodeload import (
        LowNodeLoadArgs,
        select_victims,
    )

    rng = np.random.default_rng(9)
    r = NUM_RESOURCE_DIMS
    n, p = N_NODES, 20_000
    cap = np.zeros((n, r), np.int32)
    cap[:, 0], cap[:, 1] = 32_000, 131_072
    usage = (cap * rng.uniform(0.1, 0.95, (n, r))).astype(np.int32)
    pod_node = rng.integers(0, n, p).astype(np.int32)
    pod_usage = np.zeros((p, r), np.int32)
    pod_usage[:, 0] = rng.integers(50, 2_000, p)
    pod_usage[:, 1] = rng.integers(64, 4_096, p)
    prio = rng.integers(3000, 9999, p).astype(np.int32)
    args = LowNodeLoadArgs.default()
    iters = 2

    def lnl_loop(usage, cap, pod_node, pod_usage, prio):
        valid = jnp.ones(n, bool)
        evictable = jnp.ones(p, bool)
        counters = jnp.full(n, 10, jnp.int32)

        def body(i, carry):
            acc, u = carry
            victims = select_victims(u, cap, valid, pod_node, pod_usage,
                                     prio, evictable, counters, args)
            return acc + victims.sum(), u + (victims.sum() & 1)

        acc, _ = jax.lax.fori_loop(0, iters, body, (jnp.int32(0), usage))
        return acc

    total, _ = _median_readback_seconds(
        jax.jit(lnl_loop),
        (jnp.asarray(usage), jnp.asarray(cap), jnp.asarray(pod_node),
         jnp.asarray(pod_usage), jnp.asarray(prio)), n=3)
    return {f"lownodeload_ms_per_round_{n}n_{p}p": round(
        max((total - rtt) / iters, 1e-9) * 1e3, 2)}


def _bench_colocation(rtt: float) -> dict:
    """Spark colocation e2e @ 3 nodes (BASELINE.json's kind-demo config):
    webhook admission (BE translation to batch resources) -> scheduler
    round over batch capacity -> bind, repeated over a pod stream.  Host
    control-loop throughput, not a device kernel — ``rtt`` is unused."""
    from koordinator_tpu.api import crds, extension as ext
    from koordinator_tpu.api.qos import QoSClass
    from koordinator_tpu.api.resources import resource_vector
    from koordinator_tpu.manager.webhook import (
        PodMutatingWebhook,
        PodValidatingWebhook,
    )
    from koordinator_tpu.scheduler.scheduler import Scheduler
    from koordinator_tpu.scheduler.snapshot import (
        ClusterSnapshot,
        NodeSpec,
        PodSpec,
    )

    profile = crds.ClusterColocationProfile(
        name="colo", pod_selector={"app": "spark"}, qos_class="BE",
        koordinator_priority=5500, scheduler_name="koord-scheduler")
    mutating = PodMutatingWebhook([profile])
    validating = PodValidatingWebhook()
    snapshot = ClusterSnapshot(capacity=4)
    for i in range(3):
        snapshot.upsert_node(NodeSpec(
            name=f"n{i}",
            allocatable=resource_vector({
                "cpu": 16_000, "memory": 32_768,
                ext.RESOURCE_BATCH_CPU: 12_000,
                ext.RESOURCE_BATCH_MEMORY: 24_576,
            })))
    scheduler = Scheduler(snapshot)

    pods_per_round, rounds = 60, 6
    n_scheduled = 0
    t0 = time.perf_counter()
    for r in range(rounds):
        if r == 1:  # round 0 is the jit warm-up; time the steady state
            n_scheduled, t0 = 0, time.perf_counter()
        for i in range(pods_per_round):
            pod = {
                "metadata": {"name": f"spark-{r}-{i}",
                             "namespace": "default",
                             "labels": {"app": "spark"}},
                "spec": {"containers": [{"name": "m", "resources": {
                    "requests": {"cpu": "500m", "memory": "1Gi"},
                    "limits": {"cpu": "500m", "memory": "1Gi"}}}]},
            }
            mutating.mutate(pod)
            denied = validating.validate(pod)
            if denied:
                raise RuntimeError(f"webhook rejected a bench pod: {denied}")
            req = pod["spec"]["containers"][0]["resources"]["requests"]
            scheduler.enqueue(PodSpec(
                name=pod["metadata"]["name"],
                requests=resource_vector({
                    ext.RESOURCE_BATCH_CPU: req[ext.RESOURCE_BATCH_CPU],
                    ext.RESOURCE_BATCH_MEMORY:
                        req[ext.RESOURCE_BATCH_MEMORY] // (1 << 20),
                }),
                priority=5500, qos=int(QoSClass.BE)))
        result = scheduler.schedule_round()
        n_scheduled += len(result.assignments)
        for name in result.assignments:
            scheduler.delete_pod(name)  # job completes: free for next wave
    dt = time.perf_counter() - t0
    timed = pods_per_round * (rounds - 1)      # round 0 is untimed warm-up
    if n_scheduled < timed * 0.9:
        raise RuntimeError(
            f"colocation loop scheduled only {n_scheduled}/{timed} pods")
    return {"spark_colocation_e2e_pods_per_sec_3n": round(n_scheduled / dt, 1)}


def _bench_deltasync(rtt: float) -> dict:
    """State-sync path timing: the <200ms p99 budget includes
    host->device delta application (SURVEY §7 hard part (a)).  Over
    REAL unix sockets: a 10,240-node snapshot bootstrap
    (StateSyncService -> wire -> StateSyncClient -> SchedulerBinding)
    and a 1,024-row node_usage delta burst, each ending in the
    snapshot's dirty-row device scatter (``flush``).  Host control-loop
    path — ``rtt`` is unused (flush's device put is the measured part).
    """
    import tempfile

    from koordinator_tpu.api.resources import NUM_RESOURCE_DIMS
    from koordinator_tpu.scheduler.scheduler import Scheduler
    from koordinator_tpu.scheduler.snapshot import ClusterSnapshot
    from koordinator_tpu.transport import (
        RpcClient,
        RpcServer,
        StateSyncClient,
        StateSyncService,
    )
    from koordinator_tpu.transport.deltasync import SchedulerBinding

    n_nodes, n_burst = 10_240, 1_024
    rng = np.random.default_rng(13)
    alloc = np.zeros((n_nodes, NUM_RESOURCE_DIMS), np.int32)
    alloc[:, 0] = rng.integers(8_000, 64_000, n_nodes)
    alloc[:, 1] = rng.integers(16_384, 262_144, n_nodes)
    usage = (alloc * 0.3).astype(np.int32)

    service = StateSyncService()
    for i in range(n_nodes):
        service.upsert_node(f"n{i}", alloc[i], usage=usage[i])

    out: dict = {}
    with tempfile.TemporaryDirectory() as tmp:
        server = RpcServer(os.path.join(tmp, "koord.sock"))
        service.attach(server)
        server.start()
        sched = Scheduler(ClusterSnapshot(capacity=n_nodes))
        sync = StateSyncClient(SchedulerBinding(sched))
        client = RpcClient(server.path, on_push=sync.on_push)
        client.connect()
        try:
            t0 = time.perf_counter()
            applied = sync.bootstrap(client)
            sched.snapshot.flush()
            dt = time.perf_counter() - t0
            out["deltasync_bootstrap_rows_per_sec_10240n"] = round(
                n_nodes / dt, 1)
            out["deltasync_bootstrap_wall_s"] = round(dt, 3)
            if applied != n_nodes:
                raise RuntimeError(
                    f"deltasync bootstrap applied {applied}/{n_nodes} rows")

            # usage burst: the NodeMetric refresh loop's wire shape
            burst_usage = (alloc[:n_burst] * 0.6).astype(np.int32)
            target_rv = service.rv + n_burst
            t0 = time.perf_counter()
            for i in range(n_burst):
                service.update_node_usage(f"n{i}", burst_usage[i])
            deadline = time.time() + 60
            while sync.rv < target_rv and time.time() < deadline:
                time.sleep(0.001)
            shipped = sched.snapshot.flush()
            dt = time.perf_counter() - t0
            out["deltasync_burst_rows_per_sec_1024rows"] = round(
                n_burst / dt, 1)
            out["deltasync_burst_wall_ms"] = round(dt * 1e3, 2)
            if sync.rv < target_rv:
                raise RuntimeError(
                    f"deltasync burst: client rv {sync.rv} < {target_rv} "
                    f"after 60s")
            if shipped != n_burst:
                raise RuntimeError(
                    f"deltasync burst: flush shipped {shipped} rows, "
                    f"burst was {n_burst}")
        finally:
            client.close()
            server.stop()
    return out


#: the other BASELINE.json configs, run after the headline in this same
#: process (the one that holds the chip)
EXTRA_CONFIGS = (_bench_quota, _bench_gang, _bench_lownodeload,
                 _bench_colocation, _bench_deltasync)


def main() -> None:
    from __graft_entry__ import _build_problem
    from koordinator_tpu.compile_cache import enable_compile_cache
    from koordinator_tpu.ops.assignment import score_pods
    from koordinator_tpu.ops.batch_assign import batch_assign

    enable_compile_cache()
    device = require_tpu()
    state, pods, cfg = _build_problem(N_NODES, N_PODS, seed=42)

    def rtt_floor(state, pods):
        # same traced calling convention as the timed kernels, so the
        # floor includes the pods-pytree dispatch overhead it subtracts
        return state.node_allocatable.sum() + pods.requests.sum()

    rtt, _ = _median_readback_seconds(jax.jit(rtt_floor), (state, pods))

    def score_fn(st, p):
        scores, feasible = score_pods(st, p, cfg)
        # the FULL (P, N) score tensor must stay live (scores.sum()) or XLA
        # may legally slice scoring down to the one row the chain consumes
        return (scores.sum() + feasible.sum(),
                st.replace(node_requested=st.node_requested
                           + (scores[0, :, None] & 1)))

    # k=16 with stratified (5, 15) candidates: stratified selection
    # assigns 100% of this exact shape at k=16 where a single sb=5 key
    # strands pods (docs/solve_quality.md "Stratified candidates at
    # shape"); solve_assigned_frac below guards that on every run.  Every
    # candidate method below is timed; the headline takes the fastest one
    # inside the 1%-of-best quality gate and records all, so the headline
    # is always the measured best rather than a pre-committed guess.
    score_per_iter, _ = _time_assign(state, pods, score_fn, rtt, n=5)
    # method passed EXPLICITLY so the recorded label always matches what
    # ran, whatever "auto" resolves to
    candidates = {
        "approx": lambda st, p: batch_assign(st, p, cfg, k=16,
                                             method="approx")[:2],
        # k=8 halves candidate-tensor work; the quality gate below keeps
        # it from winning if approx_max_k's recall strands pods
        "approx_k8": lambda st, p: batch_assign(st, p, cfg, k=8,
                                                method="approx")[:2],
        "chunked": lambda st, p: batch_assign(st, p, cfg, k=16,
                                              method="chunked")[:2],
        # the recall-exact form (exact top_k at chunked peak memory) —
        # timing it alongside approx prices the flip bench_recall.py's
        # decision rule would trigger
        "chunked_exact": lambda st, p: batch_assign(
            st, p, cfg, k=16, method="chunked_exact")[:2],
    }
    timed = {method: _time_assign(state, pods, fn, rtt, n=5)
             for method, fn in candidates.items()}
    # quality gates speed: only variants whose assigned count is within
    # 1% of the best may win on time — a faster solver that strands pods
    # is not an improvement
    best_count = max(t[1] for t in timed.values())
    eligible = {m: t for m, t in timed.items() if t[1] >= 0.99 * best_count}
    best = min(eligible, key=lambda m: eligible[m][0])
    solve_per_iter, solve_count = eligible[best]
    score_pods_per_sec = N_PODS / score_per_iter
    solve_pods_per_sec = N_PODS / solve_per_iter
    # solve QUALITY rides alongside throughput (the chained loop's
    # accumulator counts assigned pods, so no extra compile): the queue at
    # this shape is fully schedulable (capacity = 3.6x demand), so
    # assigned/valid must stay ~1.0
    assigned_frac = solve_count / float(pods.valid.sum())

    from koordinator_tpu.parallel import mesh as _pmesh

    extra = {
        "provenance": _git_head(),
        # every record names the device it ran on, and the 2-D axis split
        # the scheduler would solve on there (None = single-device)
        **device,
        "mesh_axes": _pmesh.mesh_axes(_pmesh.resolve_solver_mesh("auto")),
        f"filter_score_pods_per_sec_{N_PODS}p_{N_NODES}n": round(
            score_pods_per_sec, 1
        ),
        "solve_ms_per_round": round(solve_per_iter * 1e3, 2),
        "solve_assigned_frac": round(assigned_frac, 4),
        "solve_candidate_method": best,
    }
    # Per-solve latency DISTRIBUTION: BASELINE's target is <200ms p99,
    # not a chained mean.  Each sample is one single-iteration chained
    # readback minus the separately measured round-trip floor; host
    # jitter pollutes the tail, so this is an upper bound on the solver's
    # own p99.
    single = jax.jit(_chained_loop(candidates[best], iters=1))
    float(single(state, pods))  # warm/compile
    samples = []
    for _ in range(20):
        t0 = time.perf_counter()
        float(single(state, pods))
        samples.append(max(time.perf_counter() - t0 - rtt, 0.0) * 1e3)
    for q in (50, 90, 99):
        extra[f"solve_latency_ms_p{q}"] = round(
            float(np.percentile(samples, q)), 2)
    for method, t in timed.items():
        extra[f"solve_ms_{method}"] = round(t[0] * 1e3, 2)
    for config in EXTRA_CONFIGS:
        extra.update(config(rtt))

    print(
        json.dumps(
            {
                "metric": f"solve_pods_per_sec_{N_PODS}p_{N_NODES}n",
                "value": round(solve_pods_per_sec, 1),
                "unit": "pods/s",
                "vs_baseline": round(
                    solve_pods_per_sec / BASELINE_PODS_PER_SEC, 3
                ),
                "extra": extra,
            }
        )
    )


if __name__ == "__main__":
    main()
