"""Candidate-recall measurement: approx_max_k vs exact top_k.

``method="auto"`` serves ``approx`` (``jax.lax.approx_max_k``,
recall_target=0.95) on TPU, but the CPU lowering of approx_max_k is exact —
so the 100%-assignment guarantee behind the TPU default can only be
validated on the TPU.  This script produces the data that validates (or
flips) the default on the backend where it matters:

- per-pod candidate recall of ``method="approx"`` against ``method="exact"``
  at 2,048 pods x 10,240 nodes (same k, same stratified spread_bits);
- solve quality (assigned fraction + mean chosen node score) for both
  methods at that shape;
- assigned fraction at the 50k x 10,240 north-star shape for every
  candidate method.

Decision rule recorded alongside the data: if at-shape
``assigned_frac_approx`` < 0.99 on TPU, flip the TPU arm of
``ops/batch_assign.resolve_candidate_method`` to "chunked_exact" and
re-measure.

Prints ONE JSON line stamped with the platform.  The platform must be a TPU
and any failed leg fails the run; ``--smoke`` is the explicit ask to run on
whatever backend JAX has (CI: the CPU, where the recall figure is that of
an exact lowering and says nothing about the default), and prefixes every
wall-clock field ``smoke_``.  Env knobs KOORD_RECALL_NODES /
KOORD_RECALL_PODS / KOORD_RECALL_SHAPE_PODS shrink the shapes for it (the
at-shape leg is skipped when KOORD_RECALL_SHAPE_PODS=0).
"""

from __future__ import annotations

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

K = 16


def _chosen_scores(state, pods, cfg, assignments):
    """Mean raw score of each assigned pod's chosen node (score scale of
    ops/scoring.py, before ranking-key quantization)."""
    from koordinator_tpu.ops.assignment import score_pods

    scores, _ = jax.jit(score_pods)(state, pods, cfg)
    scores = np.asarray(scores)
    asn = np.asarray(assignments)
    mask = asn >= 0
    if not mask.any():
        return 0.0
    return float(scores[np.arange(len(asn))[mask], asn[mask]].mean())


def _recall_leg(n_nodes: int, n_pods: int, out: dict) -> None:
    from __graft_entry__ import _build_problem
    from koordinator_tpu.ops.batch_assign import batch_assign, select_candidates

    state, pods, cfg = _build_problem(n_nodes, n_pods, seed=42)
    sel = jax.jit(select_candidates, static_argnames=("k", "method"))
    _, exact_nodes = sel(state, pods, cfg, k=K, method="exact")
    _, approx_nodes = sel(state, pods, cfg, k=K, method="approx")
    exact_nodes = np.asarray(exact_nodes)
    approx_nodes = np.asarray(approx_nodes)

    # per-pod recall of the exact candidate SET (strata may duplicate a
    # node across slots; set semantics measure what the rounds can use)
    recalls = np.empty(n_pods, np.float64)
    for i in range(n_pods):
        e = set(exact_nodes[i].tolist())
        a = set(approx_nodes[i].tolist())
        recalls[i] = len(e & a) / max(len(e), 1)
    out[f"candidate_recall_mean_{n_pods}p_{n_nodes}n"] = round(
        float(recalls.mean()), 4)
    out[f"candidate_recall_p10_{n_pods}p_{n_nodes}n"] = round(
        float(np.percentile(recalls, 10)), 4)
    out[f"candidate_recall_min_{n_pods}p_{n_nodes}n"] = round(
        float(recalls.min()), 4)

    solve = jax.jit(batch_assign, static_argnames=("k", "method"))
    valid = float(np.asarray(pods.valid).sum())
    for method in ("exact", "approx"):
        asn, _, _ = solve(state, pods, cfg, k=K, method=method)
        frac = float((np.asarray(asn) >= 0).sum()) / valid
        out[f"assigned_frac_{method}_{n_pods}p_{n_nodes}n"] = round(frac, 4)
        out[f"mean_chosen_score_{method}_{n_pods}p_{n_nodes}n"] = round(
            _chosen_scores(state, pods, cfg, asn), 1)


def _quality_leg(n_nodes: int, n_pods: int, out: dict) -> None:
    """quality_lp vs greedy (ISSUE 13): assigned fraction, per-dim
    capacity slack after the solve, and compile-plus-first-run wall for
    both engines at one shape.  Plus the topo-gang leg:
    realized plan diameter of the baseline vs the quality planner on a
    seeded 2x2x2 topology."""
    import numpy as np

    from __graft_entry__ import _build_problem
    from koordinator_tpu.api.resources import ResourceDim
    from koordinator_tpu.ops.batch_assign import batch_assign
    from koordinator_tpu.quality.lp_pack import lp_pack_assign

    state, pods, cfg = _build_problem(n_nodes, n_pods, seed=42)
    valid = float(np.asarray(pods.valid).sum())

    def slack(st):
        free = np.asarray(st.node_allocatable - st.node_requested)
        alloc = np.asarray(st.node_allocatable)
        node_valid = np.asarray(st.node_valid)
        return {
            dim.name.lower(): round(
                float(free[node_valid, dim].sum())
                / max(float(alloc[node_valid, dim].sum()), 1.0), 4)
            for dim in ResourceDim
            if float(alloc[node_valid, dim].sum()) > 0
        }

    shape = f"{n_pods}p_{n_nodes}n"
    for name, solve in (
        ("greedy", jax.jit(lambda s: batch_assign(s, pods, cfg)[:2])),
        ("quality_lp", jax.jit(lambda s: lp_pack_assign(s, pods, cfg)[:2])),
    ):
        t0 = time.perf_counter()
        asn, st = solve(state)
        frac = float((np.asarray(asn) >= 0).sum()) / max(valid, 1.0)
        out[f"assigned_frac_{name}_{shape}"] = round(frac, 4)
        out[f"capacity_slack_{name}_{shape}"] = slack(st)
        out[f"wall_s_{name}_{shape}"] = round(
            time.perf_counter() - t0, 2)

    # topo-gang diameter: baseline vs quality planner on a seeded tree
    from koordinator_tpu.ops.network_topology import (
        TopologyRequirements,
        TopologyTree,
        plan_gang_placement,
    )
    from koordinator_tpu.quality.topo_gang import (
        plan_diameter,
        plan_gang_placement_quality,
    )
    from koordinator_tpu.state.cluster_state import ClusterState, PodBatch

    rng = np.random.default_rng(42)
    tree = TopologyTree(["spine", "block", "node"])
    t_nodes = 8
    for i in range(t_nodes):
        tree.add_node([f"s{i // 4}", f"b{i // 2}", f"n{i}"])
    topo = tree.build()
    alloc = np.zeros((t_nodes, jnp.asarray(pods.requests).shape[1]),
                     np.int32)
    alloc[:, 0] = rng.integers(2_000, 9_000, t_nodes)
    alloc[:, 1] = 65_536
    t_state = ClusterState.from_arrays(alloc)
    members = 3
    req = np.zeros((members, alloc.shape[1]), np.int32)
    req[:, 0] = 2_000
    req[:, 1] = 1_024
    g_pods = PodBatch.build(req, node_capacity=t_nodes)
    mask = np.zeros(g_pods.capacity, bool)
    mask[:members] = True
    existing = jnp.asarray(rng.integers(0, 2, t_nodes).astype(np.int32))
    treq = TopologyRequirements(desired_slots=members)
    for name, plan_fn in (("baseline", plan_gang_placement),
                          ("quality", plan_gang_placement_quality)):
        plan = plan_fn(t_state, g_pods, mask, topo, treq,
                       node_existing=existing)
        out[f"gang_topo_diameter_{name}"] = plan_diameter(plan, topo)


def _at_shape_leg(n_nodes: int, n_pods: int, out: dict) -> None:
    from __graft_entry__ import _build_problem
    from koordinator_tpu.ops.batch_assign import batch_assign

    state, pods, cfg = _build_problem(n_nodes, n_pods, seed=42)
    valid = float(np.asarray(pods.valid).sum())
    solve = jax.jit(batch_assign, static_argnames=("k", "method"))
    for method in ("approx", "chunked", "chunked_exact", "exact"):
        t0 = time.perf_counter()
        asn, _, _ = solve(state, pods, cfg, k=K, method=method)
        frac = float((np.asarray(asn) >= 0).sum()) / valid
        out[f"shape_assigned_frac_{method}_{n_pods}p_{n_nodes}n"] = (
            round(frac, 4))
        # compile + first run: a set-up read-out, not a solve time
        out[f"shape_wall_s_{method}_{n_pods}p_{n_nodes}n"] = round(
            time.perf_counter() - t0, 1)


def main() -> None:
    from bench import _git_head, require_tpu
    from koordinator_tpu.compile_cache import enable_compile_cache

    smoke = "--smoke" in sys.argv
    enable_compile_cache()
    device = require_tpu(allow_cpu=smoke)
    n_nodes = int(os.environ.get("KOORD_RECALL_NODES", "10240"))
    n_pods = int(os.environ.get("KOORD_RECALL_PODS", "2048"))
    shape_pods = int(os.environ.get("KOORD_RECALL_SHAPE_PODS", "50000"))

    out: dict = {
        **device,
        "provenance": _git_head(),
        "k": K,
        "note": "approx_max_k recall vs exact top_k; CPU lowering of "
                "approx_max_k is exact, so only a tpu backend row "
                "validates the method='auto' TPU default",
        "decision_rule": "flip auto's TPU arm from 'approx' to "
                         "'chunked_exact' if shape_assigned_frac_approx "
                         "< 0.99 on tpu",
    }
    _recall_leg(n_nodes, n_pods, out)
    # quality leg at the recall shape (KOORD_RECALL_QUALITY=0 skips):
    # the solve-quality comparison ROADMAP item 4 benches against —
    # assigned fraction + capacity slack per dim + gang topo diameter
    if int(os.environ.get("KOORD_RECALL_QUALITY", "1")):
        _quality_leg(n_nodes, n_pods, out)
    if shape_pods:
        _at_shape_leg(n_nodes, shape_pods, out)
    if smoke:
        out = {(f"smoke_{k}" if "wall_s" in k else k): v
               for k, v in out.items()}
    print(json.dumps(out))


if __name__ == "__main__":
    main()
