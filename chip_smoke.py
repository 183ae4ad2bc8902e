"""chip_smoke.py: the served scheduling path, once, on the chip.

Leg 1 assembles ``koord-scheduler`` through its normal entry point with a
listen socket, and a client in this same process speaks the wire protocol
to it: every node and pod enters as a ``STATE_PUSH`` frame, ``solve_remote``
drains the backlog, then steady-state rounds (node_usage updates, pods
arriving and leaving over a standing unschedulable backlog) run the
incremental path.  Leg 2 assembles a ``Scheduler`` with gangs and a quota
tree the way ``tools/loadgen.py`` does and runs one gang + quota round.

What comes back is checked against a plain numpy reference that shares
nothing with the solver: per-node requested recomputed in int64 from the
returned assignments must stay <= allocatable on every dim, every pod is
bound or carries a diagnosis, every gang is all-or-nothing, every quota's
used stays <= its runtime and max.  Any failed check or phase raises: there
is no path from a failure to exit code 0.

Fails unless JAX reports a TPU.  ``--cpu-dry-run`` runs the same phases at a
tiny shape on whatever backend JAX has (tier-1 uses it on the CPU); it says
so in its device, read-out and result lines and never prints the chip's
result line.

The time and memory read-outs are a smoke's set-up facts (does it compile,
does it fit, how long until the first answer), not benchmark metrics.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np

#: the shape the paper names (10,240 nodes, 50,000 pending pods), plus a
#: standing backlog of pods that fit no node: it keeps steady-state rounds
#: above the scheduler's batch-solver threshold, which is what the
#: incremental candidate path needs to engage
REAL = dict(nodes=10_240, pods=50_000, backlog=2_048, arrive=300, leave=500,
            usage_nodes=102, gang_nodes=1_024, gangs=256, gang_size=16,
            quota_pods=5_000, quota_leaves=64)
DRY = dict(nodes=1_024, pods=1_000, backlog=1_024, arrive=40, leave=60,
           usage_nodes=10, gang_nodes=128, gangs=32, gang_size=16,
           quota_pods=512, quota_leaves=8)
#: the backlog at this shape (capacity ~3.6x demand) must bind this fast
MAX_DRAIN_ROUNDS = 3
#: steady rounds: the first ones settle (every node is dirty right after
#: the drain, and the incremental programs compile once); the last
#: WARM_ROUNDS must all be incremental with no recompile
SETTLE_ROUNDS = 2
WARM_ROUNDS = 3
#: a wire client's timeout must cover the in-line compile of a cold round
COLD_TIMEOUT_S = 1_000.0


class SmokeError(Exception):
    """A check of the smoke did not hold."""


def require(cond, message: str) -> None:
    if not cond:
        raise SmokeError(message)


def say(tag: str, **fields) -> None:
    print(f"{tag} {json.dumps(fields, sort_keys=True)}", flush=True)


# -- data, made in bulk from the seed ---------------------------------------

def make_nodes(rng, n: int) -> tuple[np.ndarray, np.ndarray]:
    """(alloc, usage), each (n, R) int32, in ``_build_problem``'s value
    ranges; batch dims stand in for the manager's colocation output."""
    from koordinator_tpu.api.resources import NUM_RESOURCE_DIMS, ResourceDim

    alloc = np.zeros((n, NUM_RESOURCE_DIMS), np.int32)
    alloc[:, ResourceDim.CPU] = rng.integers(8_000, 64_000, n)
    alloc[:, ResourceDim.MEMORY] = rng.integers(16_384, 262_144, n)
    alloc[:, ResourceDim.BATCH_CPU] = alloc[:, ResourceDim.CPU] // 2
    alloc[:, ResourceDim.BATCH_MEMORY] = alloc[:, ResourceDim.MEMORY] // 2
    return alloc, make_usage(rng, alloc)


def make_usage(rng, alloc: np.ndarray) -> np.ndarray:
    return (alloc * rng.random(alloc.shape) * 0.5).astype(np.int32)


def make_pods(rng, n: int) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(requests (n, R) int32, priority (n,), qos (n,)): a quarter BE pods
    in the batch priority band asking for batch resources, the rest LS
    pods spread over the prod and mid bands."""
    from koordinator_tpu.api.qos import QoSClass
    from koordinator_tpu.api.resources import NUM_RESOURCE_DIMS, ResourceDim

    cpu = rng.integers(100, 4_000, n)
    mem = rng.integers(128, 8_192, n)
    be = rng.random(n) < 0.25
    req = np.zeros((n, NUM_RESOURCE_DIMS), np.int32)
    req[:, ResourceDim.CPU] = np.where(be, 0, cpu)
    req[:, ResourceDim.MEMORY] = np.where(be, 0, mem)
    req[:, ResourceDim.BATCH_CPU] = np.where(be, cpu, 0)
    req[:, ResourceDim.BATCH_MEMORY] = np.where(be, mem, 0)
    prod = rng.random(n) < 0.5
    prio = np.where(be, rng.integers(5_000, 6_000, n),
                    np.where(prod, rng.integers(9_000, 10_000, n),
                             rng.integers(7_000, 8_000, n)))
    qos = np.where(be, int(QoSClass.BE), int(QoSClass.LS))
    return req, prio.astype(np.int64), qos.astype(np.int64)


def whale_request() -> np.ndarray:
    """More CPU than any node has: fits nowhere, must carry a diagnosis."""
    from koordinator_tpu.api.resources import resource_vector

    return resource_vector(cpu=100_000, memory=1_024)


# -- the plain reference ----------------------------------------------------

def check_no_overcommit(alloc: np.ndarray, node_names: list[str],
                        requests: dict[str, np.ndarray],
                        bound: dict[str, str]) -> int:
    """Recompute per-node requested from the assignments alone, in numpy
    int64, and require <= allocatable on every node and dim."""
    row = {name: i for i, name in enumerate(node_names)}
    unknown = [n for n in set(bound.values()) if n not in row]
    require(not unknown, f"pods bound to unknown nodes: {unknown[:3]}")
    rows = np.fromiter((row[node] for node in bound.values()), np.int64,
                       len(bound))
    req = (np.stack([requests[p] for p in bound]).astype(np.int64)
           if bound else np.zeros((0, alloc.shape[1]), np.int64))
    requested = np.zeros(alloc.shape, np.int64)
    np.add.at(requested, rows, req)
    over = np.argwhere(requested > alloc.astype(np.int64))
    if over.size:
        raise SmokeError(
            f"{len(over)} (node, dim) cells over allocatable, first: node "
            f"{node_names[over[0][0]]} dim {over[0][1]}")
    return len(bound)


def check_gangs(members: dict[str, list[str]], min_member: int,
                bound: dict[str, str]) -> tuple[int, int]:
    """Each gang is bound whole (>= min_member) or not at all."""
    whole = 0
    for gang, pods in members.items():
        n = sum(1 for p in pods if p in bound)
        require(n == 0 or n >= min_member,
                f"gang {gang} partially bound: {n}/{len(pods)} "
                f"(min_member {min_member})")
        whole += n >= min_member
    return whole, len(members) - whole


def check_quotas(pod_quota: dict[str, str], requests: dict[str, np.ndarray],
                 bound: dict[str, str], limits: dict[str, np.ndarray],
                 what: str) -> None:
    """Per-quota used, summed in int64 from the assignments, <= limit on
    the dims the limit bounds (a negative limit is unbounded)."""
    used = {q: np.zeros_like(lim, dtype=np.int64)
            for q, lim in limits.items()}
    for pod in bound:
        quota = pod_quota.get(pod)
        if quota is not None:
            used[quota] += requests[pod].astype(np.int64)
    for quota, lim in limits.items():
        bounded = lim >= 0
        require(bool(np.all(used[quota][bounded] <= lim[bounded])),
                f"quota {quota} used {used[quota].tolist()} over its {what} "
                f"{lim.tolist()}")


# -- what ran ---------------------------------------------------------------

def recompiles_total() -> int:
    from koordinator_tpu import metrics

    return int(sum(v for _, v in metrics.solver_recompiles.items()))


def check_mesh(scheduler, n_devices: int) -> dict:
    """On N > 1 devices the solve must be on the mesh, with the state's
    node tensors on N distinct devices, not everything on device 0."""
    state = scheduler.snapshot.resident_state   # placement only
    kit = scheduler.kit
    placed = {s.device.id for s in state.node_allocatable.addressable_shards}
    if n_devices > 1:
        require(kit.shards == n_devices,
                f"solver shards {kit.shards} != {n_devices} devices")
        require(kit.sharding_active_for(state.capacity),
                "solver sharding not active on a multi-device host")
        require(len(placed) == n_devices,
                f"node tensors on {len(placed)} device(s), want {n_devices}")
        rows = {s.data.shape[0]
                for s in state.node_allocatable.addressable_shards}
        require(rows == {state.capacity // n_devices},
                f"uneven node shards: {sorted(rows)}")
    return {"shards": kit.shards if n_devices > 1 else 1,
            "devices_holding_nodes": len(placed)}


def peak_bytes() -> int | None:
    import jax

    stats = jax.devices()[0].memory_stats()
    return None if not stats else int(stats["peak_bytes_in_use"])


# -- leg 1: the served path over the wire -----------------------------------

def served_leg(shape: dict, seed: int, n_devices: int) -> dict:
    import jax

    from koordinator_tpu.api.qos import QoSClass
    from koordinator_tpu.cmd.binaries import MAINS
    from koordinator_tpu.transport import RpcClient
    from koordinator_tpu.transport.services import solve_remote
    from koordinator_tpu.transport.wire import FrameType

    LS = int(QoSClass.LS)
    rng = np.random.default_rng(seed)
    n, p = shape["nodes"], shape["pods"]
    alloc, usage = make_nodes(rng, n)
    req, prio, qos = make_pods(rng, p)
    node_names = [f"n{i}" for i in range(n)]
    requests: dict[str, np.ndarray] = {}
    bound: dict[str, str] = {}
    pending: set[str] = set()
    out: dict = {}

    with tempfile.TemporaryDirectory(prefix="koord-smoke-") as tmp:
        sock = os.path.join(tmp, "sched.sock")
        assembled = MAINS["koord-scheduler"]([
            "--disable-leader-election", "--node-capacity", str(n),
            "--listen-socket", sock])
        scheduler = assembled.component
        client = RpcClient(sock, timeout=COLD_TIMEOUT_S)
        try:
            client.connect()

            def push(doc: dict, arrays: dict | None = None) -> None:
                client.call(FrameType.STATE_PUSH, doc, arrays)

            def add_pod(name, request, priority, qos_class) -> None:
                requests[name] = request
                pending.add(name)
                push({"kind": "pod_add", "name": name,
                      "priority": int(priority), "qos": int(qos_class)},
                     {"requests": request})

            def solve(label: str) -> dict:
                t0 = time.perf_counter()
                doc = solve_remote(client)
                with scheduler.lock:
                    jax.block_until_ready(scheduler.snapshot.state)
                wall = time.perf_counter() - t0
                for pod, node in doc["assignments"].items():
                    require(pod in pending, f"{pod} bound but not pending")
                    pending.discard(pod)
                    bound[pod] = node
                undiagnosed = pending - set(doc["failures"])
                require(not undiagnosed,
                        f"{len(undiagnosed)} pending pods neither bound nor "
                        f"diagnosed, e.g. {sorted(undiagnosed)[:3]}")
                check_no_overcommit(alloc, node_names, requests, bound)
                require(scheduler.last_solver == "batch",
                        f"{label}: solver {scheduler.last_solver!r}, "
                        f"want 'batch'")
                say("ROUND", label=label, wall_s=round(wall, 3),
                    bound_now=len(doc["assignments"]),
                    pending=len(pending), path=scheduler.last_solve_path,
                    recompiles_total=recompiles_total())
                return {"wall_s": wall, "path": scheduler.last_solve_path,
                        "bound_now": len(doc["assignments"])}

            # -- load: every node and pod enters as a STATE_PUSH frame
            t0 = time.perf_counter()
            for i, name in enumerate(node_names):
                push({"kind": "node_upsert", "name": name},
                     {"allocatable": alloc[i], "usage": usage[i]})
            for i in range(p):
                add_pod(f"p{i}", req[i], prio[i], qos[i])
            whale = whale_request()
            for i in range(shape["backlog"]):
                add_pod(f"whale{i}", whale, 9_500, LS)
            out["load_s"] = time.perf_counter() - t0
            say("LOADED", nodes=n, pods=p, standing_backlog=shape["backlog"],
                load_s=round(out["load_s"], 2))

            # -- drain: the cold round compiles in-line
            rounds = []
            while len(bound) < p:
                require(len(rounds) < MAX_DRAIN_ROUNDS,
                        f"only {len(bound)}/{p} bound after "
                        f"{MAX_DRAIN_ROUNDS} rounds")
                rounds.append(solve(f"drain{len(rounds)}"))
            require(rounds[0]["path"] == "full_cold",
                    f"first round took {rounds[0]['path']!r}, "
                    f"want 'full_cold'")
            require(pending == {f"whale{i}"
                                for i in range(shape["backlog"])},
                    "pending set is not exactly the standing backlog")
            out["first_round_s"] = rounds[0]["wall_s"]
            out["drain_rounds"] = len(rounds)
            out["mesh"] = check_mesh(scheduler, n_devices)
            # what "auto" ran as, from the scheduler's own candidate cache
            out["candidate_method"] = scheduler._cand_cache["method"]

            # -- steady state: usage updates, arrivals and departures over
            # the standing backlog; the dirty-row and dirty-pod counts are
            # held in one shape bucket so warm rounds cannot recompile
            warm = []
            serial = 0
            for r in range(SETTLE_ROUNDS + WARM_ROUNDS):
                touched = rng.choice(n, shape["usage_nodes"], replace=False)
                new_usage = make_usage(rng, alloc[touched])
                for j, row in enumerate(touched):
                    push({"kind": "node_usage", "name": node_names[row]},
                         {"usage": new_usage[j]})
                busy = {node_names[row] for row in touched}
                leaving = []
                for pod in rng.permutation(sorted(bound)):
                    if bound[pod] not in busy:
                        busy.add(bound[pod])
                        leaving.append(pod)
                        if len(leaving) == shape["leave"]:
                            break
                for pod in leaving:
                    push({"kind": "pod_remove", "name": pod})
                    del bound[pod]
                a_req, a_prio, a_qos = make_pods(rng, shape["arrive"])
                for j in range(shape["arrive"]):
                    add_pod(f"a{serial}", a_req[j], a_prio[j], a_qos[j])
                    serial += 1
                before = recompiles_total()
                res = solve(f"steady{r}")
                res["recompiles"] = recompiles_total() - before
                require(res["bound_now"] == shape["arrive"],
                        f"steady{r}: {res['bound_now']}/{shape['arrive']} "
                        f"arrivals bound")
                if r >= SETTLE_ROUNDS:
                    warm.append(res)
            for i, res in enumerate(warm):
                require(res["path"] == "incremental",
                        f"warm round {i} took {res['path']!r}, "
                        f"want 'incremental'")
                require(res["recompiles"] == 0,
                        f"warm round {i} recompiled {res['recompiles']} "
                        f"program(s)")
            out["warm_round_s"] = float(np.median(
                [res["wall_s"] for res in warm]))
        finally:
            # an in-flight round (a client that gave up mid-compile) must
            # finish before teardown, or the interpreter exits under it
            with scheduler.lock:
                pass
            client.close()
            assembled.stop()
    return out


# -- leg 2: one gang + quota round ------------------------------------------

def gang_quota_leg(shape: dict, seed: int) -> dict:
    import jax

    from koordinator_tpu.api.resources import ResourceDim
    from koordinator_tpu.quota.tree import UNBOUNDED, QuotaTree
    from koordinator_tpu.scheduler import ClusterSnapshot, Scheduler
    from koordinator_tpu.scheduler.scheduler import GangRecord
    from koordinator_tpu.transport.deltasync import (
        SchedulerBinding,
        StateSyncService,
    )

    rng = np.random.default_rng(seed + 1)
    n = shape["gang_nodes"]
    alloc, usage = make_nodes(rng, n)
    node_names = [f"g{i}" for i in range(n)]
    total = alloc.sum(axis=0, dtype=np.int64)

    # 64 leaves; every other one is tight (a pod share that outruns its
    # max), so admission really rejects
    leaves = shape["quota_leaves"]
    per_leaf = shape["quota_pods"] // leaves
    tree = QuotaTree(total)
    quota_max: dict[str, np.ndarray] = {}
    for q in range(leaves):
        share = 4 if q % 2 else 1     # tight leaves admit about a quarter
        mx = np.full(total.shape, UNBOUNDED, np.int64)
        mx[ResourceDim.CPU] = per_leaf * 2_050 // share
        mx[ResourceDim.BATCH_CPU] = per_leaf * 2_050 // share
        tree.add(f"q{q}", min=np.zeros_like(total), max=mx)
        quota_max[f"q{q}"] = mx

    scheduler = Scheduler(ClusterSnapshot(capacity=n), quota_tree=tree)
    sync = StateSyncService()
    sync.attach_binding(SchedulerBinding(scheduler))
    for i, name in enumerate(node_names):
        sync.upsert_node(name, alloc[i], usage=usage[i])

    requests: dict[str, np.ndarray] = {}
    pod_quota: dict[str, str] = {}
    members: dict[str, list[str]] = {}
    size = shape["gang_size"]
    g_req, g_prio, g_qos = make_pods(rng, shape["gangs"] * size)
    whale = whale_request()
    impossible = set()
    for g in range(shape["gangs"]):
        gang = f"gang{g}"
        scheduler.register_gang(GangRecord(name=gang, min_member=size))
        members[gang] = []
        for m in range(size):
            i = g * size + m
            name = f"{gang}-m{m}"
            # one member in every 16th gang fits nowhere: the whole gang
            # must stay unbound
            if g % 16 == 0 and m == 0:
                request = whale
                impossible.add(gang)
            else:
                request = g_req[i]
            requests[name] = request
            members[gang].append(name)
            sync.add_pod(name, request, priority=int(g_prio[i]), gang=gang,
                         qos=int(g_qos[i]))
    q_req, q_prio, q_qos = make_pods(rng, shape["quota_pods"])
    for i in range(shape["quota_pods"]):
        name = f"qp{i}"
        requests[name] = q_req[i]
        pod_quota[name] = f"q{i % leaves}"
        sync.add_pod(name, q_req[i], priority=int(q_prio[i]),
                     quota=pod_quota[name], qos=int(q_qos[i]))

    t0 = time.perf_counter()
    with scheduler.lock:
        result = scheduler.schedule_round()
        jax.block_until_ready(scheduler.snapshot.state)
    wall = time.perf_counter() - t0
    bound = dict(result.assignments)

    require(scheduler.last_solver == "batch",
            f"gang round solver {scheduler.last_solver!r}, want 'batch'")
    require(scheduler.last_solve_path == "full_gang",
            f"gang round took {scheduler.last_solve_path!r}, "
            f"want 'full_gang'")
    undiagnosed = set(requests) - set(bound) - set(result.failures)
    require(not undiagnosed,
            f"{len(undiagnosed)} pods neither bound nor diagnosed")
    check_no_overcommit(alloc, node_names, requests, bound)
    whole, unbound = check_gangs(members, size, bound)
    for gang in impossible:
        require(not any(p in bound for p in members[gang]),
                f"{gang} has a member that fits nowhere yet was bound")
    require(whole * 2 >= len(members) - len(impossible),
            f"only {whole} of {len(members) - len(impossible)} feasible "
            f"gangs bound")
    check_quotas(pod_quota, requests, bound, quota_max, "max")
    runtime = {q: np.where(quota_max[q] >= 0, tree.runtime_of(q), -1)
               for q in quota_max}
    check_quotas(pod_quota, requests, bound, runtime, "runtime")
    q_bound = sum(1 for p in bound if p in pod_quota)
    require(0 < q_bound < shape["quota_pods"],
            f"quota admission bound {q_bound}/{shape['quota_pods']}: the "
            f"tight leaves should reject some and admit some")
    scheduler.stop()
    out = {"wall_s": wall, "gangs_bound_whole": whole,
           "gangs_unbound": unbound, "quota_pods_bound": q_bound,
           "path": scheduler.last_solve_path}
    say("ROUND", label="gang_quota", **{k: (round(v, 3)
                                            if isinstance(v, float) else v)
                                        for k, v in out.items()})
    return out


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument(
        "--cpu-dry-run", action="store_true",
        help="tiny shape on whatever backend JAX has; proves control flow "
             "and the checks, says nothing about the chip")
    args = parser.parse_args(argv)

    from koordinator_tpu.compile_cache import (
        cache_events,
        enable_compile_cache,
    )

    cache_dir = enable_compile_cache()
    cache = cache_events()
    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    say("DEVICE", **device, dry_run=args.cpu_dry_run)
    if device["platform"] != "tpu" and not args.cpu_dry_run:
        raise SystemExit(
            f"chip_smoke: platform is {device['platform']!r}, not 'tpu'; "
            f"refusing to run (--cpu-dry-run is the tiny CPU rehearsal)")

    shape = DRY if args.cpu_dry_run else REAL
    t0 = time.perf_counter()
    served = served_leg(shape, args.seed, len(devices))
    gang = gang_quota_leg(shape, args.seed)
    say("SMOKE_READOUTS",
        note="set-up facts of a smoke run, not benchmark metrics",
        dry_run=args.cpu_dry_run, device=device,
        candidate_method=served["candidate_method"], mesh=served["mesh"],
        load_s=round(served["load_s"], 2),
        first_round_s=round(served["first_round_s"], 2),
        drain_rounds=served["drain_rounds"],
        warm_round_wall_s=round(served["warm_round_s"], 4),
        gang_quota_round_s=round(gang["wall_s"], 2),
        peak_bytes_in_use=peak_bytes(),
        compile_cache={"dir": cache_dir, **cache},
        total_s=round(time.perf_counter() - t0, 1),
        claim=None)
    print(json.dumps({"ok": True, **({"dry_run": True}
                                     if args.cpu_dry_run else {}),
                      "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
