"""Network-topology-aware gang placement.

The reference packs a gang onto the network topology tree (spine/block/node
from the ClusterNetworkTopology CRD) by: computing per-node "offer slots" (how
many gang pods fit), aggregating slots/scores/existing-pod counts up the tree,
rounding slots down to per-layer pod-count multiples, picking the deepest
topology node that can hold the whole gang (preferring subtrees with existing
peer pods, then tighter fit, then score), and recursively distributing slots
(``coscheduling/core/network_topology_solver.go:53 PlacePods``, ``:239
constrainOfferSlotByPodCountMultiple``, ``:303 searchOfferSlotSatisfiedNodes``,
``:353 distributeOfferSlot``; tree built per
``frameworkext/networktopology/tree.go:43``).

TPU-native split: everything O(nodes) or O(nodes x pods) — offer-slot
computation, tree aggregation via one segment-sum over ancestor paths,
layer-multiple rounding, candidate eligibility and lexicographic ranking — is
batched JAX. The final recursive walk over the *chosen* subtree is host-side
numpy: it touches only T topology nodes (hundreds), not the N x P problem.

Tree encoding: T topology nodes across L layers (0 = cluster root, L-1 =
physical-node layer). ``topo_parent`` (T,) parent ids (root points at itself);
``node_path`` (N, L) gives every physical node's ancestor chain, so one
segment-sum of tiled per-node values aggregates the whole tree at once.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

from koordinator_tpu.state.cluster_state import ClusterState, PodBatch


@struct.dataclass
class TopologyArrays:
    """Device-side encoding of the ClusterNetworkTopology tree."""

    topo_layer: jax.Array   # (T,) int32 layer index of each topology node
    topo_parent: jax.Array  # (T,) int32 parent topo id; root -> itself
    node_path: jax.Array    # (N, L) int32 ancestor topo id per layer
    node_topo: jax.Array    # (N,) int32 leaf topo id of each physical node
    topo_to_node: jax.Array  # (T,) int32 physical node index for leaf topo ids, -1 otherwise

    @property
    def num_topo(self) -> int:
        return self.topo_layer.shape[0]

    @property
    def num_layers(self) -> int:
        return self.node_path.shape[1]


@dataclasses.dataclass(frozen=True)
class TopologyRequirements:
    """Gang network requirements (mirrors JobTopologyRequirements,
    ``network_topology_types.go:33``)."""

    desired_slots: int
    must_gather_layer: int = -1       # layer index; -1 = whole cluster
    layer_multiples: tuple = ()       # (L,) pod-count multiple per layer (1 = none)


#: ``node_path`` entry of a layer a node carries no label for: past every
#: topology id, so the segment sums up the tree drop it
NO_DOMAIN = 2**30


class TopologyTree:
    """Host-side tree builder: nodes join by their label path (parent->child
    layer names), as tree.AddNode derives TreeNodeMeta from node labels
    (``networktopology/tree.go:108,141``).

    A domain is keyed by its whole path from the root, so two racks of one
    name under different pods are two racks.  Topology ids are handed out in
    order of first appearance and never reused: a tree only grows.  A node
    without a label at some layer hangs from the deepest layer it has one
    for; a node with none joins at the root."""

    def __init__(self, layer_names: list[str]):
        # layer_names: top-down, excluding the implicit cluster root and
        # including the node layer last, e.g. ["spine", "block", "node"].
        self.layer_names = ["cluster", *layer_names]
        self.num_layers = len(self.layer_names)
        self._index: dict[tuple, int] = {(): 0}
        self._parent = [0]
        self._layer = [0]
        #: physical node row -> its (L,) ancestor ids
        self._paths: dict[int, np.ndarray] = {}

    @property
    def num_topo(self) -> int:
        return len(self._parent)

    def add_node(self, path: list[str]) -> int:
        """Register a physical node by its label path (one name per non-root
        layer; the last entry is the node's own name) at the next row.
        Returns node index."""
        row = max(self._paths, default=-1) + 1
        self.set_node(row, path)
        return row

    def set_node(self, row: int, path: list) -> None:
        """(Re)place physical node ``row`` by its label path; ``None`` where
        the node has no label for that layer (never for the node itself)."""
        if len(path) != self.num_layers - 1:
            raise ValueError(f"path needs {self.num_layers - 1} entries, got {len(path)}")
        if path[-1] is None:
            raise ValueError("a node's own name cannot be missing")
        ids = np.full(self.num_layers, NO_DOMAIN, np.int32)
        ids[0] = 0
        parent, prefix = 0, ()
        for depth, name in enumerate(path, start=1):
            if name is None:
                continue
            prefix = (*prefix, (depth, name))
            tid = self._index.get(prefix)
            if tid is None:
                tid = len(self._parent)
                self._index[prefix] = tid
                self._parent.append(parent)
                self._layer.append(depth)
            ids[depth] = tid
            parent = tid
        self._paths[row] = ids

    def drop_node(self, row: int) -> None:
        """Row ``row`` holds no node any more; its leaf stays, empty."""
        self._paths.pop(row, None)

    def rows(self) -> dict[int, np.ndarray]:
        return self._paths

    def build(self, capacity: int | None = None) -> TopologyArrays:
        n = max(self._paths, default=-1) + 1
        cap = capacity if capacity is not None else n
        if cap < n:
            raise ValueError(f"capacity {cap} under the tree's {n} rows")
        t = len(self._parent)
        node_path = np.zeros((cap, self.num_layers), np.int32)
        node_topo = np.zeros(cap, np.int32)
        topo_to_node = np.full(t, -1, np.int32)
        for row, ids in self._paths.items():
            node_path[row] = ids
            node_topo[row] = ids[-1]
            topo_to_node[ids[-1]] = row
        return TopologyArrays(
            topo_layer=jnp.asarray(self._layer, jnp.int32),
            topo_parent=jnp.asarray(self._parent, jnp.int32),
            node_path=jnp.asarray(node_path),
            node_topo=jnp.asarray(node_topo),
            topo_to_node=jnp.asarray(topo_to_node),
        )


def gang_offer_slots(
    state: ClusterState,
    gang_requests: jnp.ndarray,
    node_valid: jnp.ndarray,
    cfg=None,
) -> jnp.ndarray:
    """(N,) int32: how many gang pods fit on each node, replacing the
    sequential filter-and-add loop (``network_topology_solver.go:113
    calculateNodeOfferSlot``) with a prefix-sum feasibility test.

    ``gang_requests`` is the (P, R) request matrix of the gang's pods (invalid
    rows zero). Slots on node n = the longest prefix of the pod list whose
    cumulative request fits the node's free capacity. When ``cfg`` (a
    ScoringConfig) is given, the k-th slot must also pass the load-aware
    usage thresholds with k pods' estimated usage added — the reference
    computes slots by running the FULL filter chain per added pod, so a plan
    never pins a pod onto a node the solver would then reject.
    """
    return _offer_slots(state.node_allocatable, state.node_requested,
                        state.node_usage, state.node_agg_usage,
                        gang_requests, node_valid, cfg)


def _offer_slots(alloc, requested, usage, agg_usage, gang_requests,
                 node_valid, cfg, n_members=None):
    """:func:`gang_offer_slots` on the accounting's rows; at most
    ``n_members`` slots a node (the reference's loop runs over the gang's
    pods only)."""
    free = alloc - requested  # (N, R)
    cum = jnp.cumsum(gang_requests, axis=0)  # (P, R)
    # fits[n, p] = pods[0..p] all fit on node n simultaneously
    fits = jnp.all(cum[None, :, :] <= free[:, None, :], axis=-1)
    if n_members is not None:
        fits = fits & (jnp.arange(gang_requests.shape[0]) < n_members)[None, :]
    if cfg is not None:
        from koordinator_tpu.ops import scoring
        from koordinator_tpu.ops.assignment import _threshold_mask

        est = scoring.estimate_pod_usage_by_band(
            gang_requests, cfg.estimator_factors, cfg.estimator_defaults
        )
        thr = _threshold_mask(
            cfg, usage, agg_usage, alloc, jnp.cumsum(est, axis=0),
        )  # (P, N)
        fits = fits & thr.T
    prefix = jnp.cumprod(fits.astype(jnp.int32), axis=1)
    return jnp.where(node_valid, prefix.sum(axis=1), 0).astype(jnp.int32)


def aggregate_tree(
    topo: TopologyArrays,
    offer_slots: jnp.ndarray,
    node_scores: jnp.ndarray,
    node_existing: jnp.ndarray,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Sum per-physical-node values into every ancestor topology node in one
    segment-sum over the tiled (N*L,) ancestor paths
    (``network_topology_solver.go:212 evaluateTopologyNode``).  A layer a
    node has no label for (``NO_DOMAIN``) is dropped by the sum."""
    t = topo.num_topo
    n = topo.node_path.shape[0]  # tree node capacity; state may be padded larger
    seg = topo.node_path.reshape(-1)  # (N*L,)

    def up(v):
        tiled = jnp.repeat(v[:n], topo.num_layers)
        return jax.ops.segment_sum(tiled, seg, num_segments=t)

    return up(offer_slots), up(node_scores), up(node_existing)


def constrain_multiples(
    topo: TopologyArrays, topo_slots: jnp.ndarray, layer_multiples: jnp.ndarray
) -> jnp.ndarray:
    """Bottom-up rounding of each topology node's slots to its layer's
    pod-count multiple (``network_topology_solver.go:249
    doConstrainOfferSlot``): a node's slots become the sum of its children's
    constrained slots, rounded down to the layer multiple.  A child may sit
    more than one layer down (a node with no label for the layers between);
    it is final by the time its parent's layer is rebuilt."""
    t = topo.num_topo
    num_layers = layer_multiples.shape[0]
    slots = topo_slots
    parent_layer = topo.topo_layer[topo.topo_parent]
    is_child = topo.topo_parent != jnp.arange(t)

    def round_layer(s, layer):
        m = jnp.maximum(layer_multiples[layer], 1)
        at_layer = topo.topo_layer == layer
        return jnp.where(at_layer, (s // m) * m, s)

    # Leaf layer rounds in place; each upper layer is rebuilt from children.
    slots = round_layer(slots, num_layers - 1)
    for layer in range(num_layers - 2, -1, -1):
        child = is_child & (parent_layer == layer)
        summed = jax.ops.segment_sum(
            jnp.where(child, slots, 0), topo.topo_parent, num_segments=t
        )
        slots = jnp.where(topo.topo_layer == layer, summed, slots)
        slots = round_layer(slots, layer)
    return slots


def eligible_candidates(
    topo: TopologyArrays,
    topo_slots: jnp.ndarray,
    desired: jnp.ndarray,
    must_gather_layer: jnp.ndarray,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(ok, deepest_layer): ok marks topology nodes reachable by descending
    only through slot-satisfied ancestors from the must-gather layer
    (``network_topology_solver.go:272,303``); deepest_layer is the lowest
    layer containing any candidate — the reference keeps only the last
    (deepest) satisfied layer's candidates."""
    sat = topo_slots >= desired
    start_layer = jnp.maximum(must_gather_layer, 0)  # -1 = whole cluster
    # Descend: ok at the start layer = sat; below = sat & ok(parent).
    ok = sat & (topo.topo_layer == start_layer)
    num_layers = int(topo.node_path.shape[1])
    for _ in range(num_layers - 1):
        ok = ok | (sat & ok[topo.topo_parent] & (topo.topo_layer > start_layer))
    deepest = jnp.max(jnp.where(ok, topo.topo_layer, -1))
    return ok & (topo.topo_layer == deepest), deepest


def _ancestor_chain_keys(topo: TopologyArrays, values: jnp.ndarray) -> jnp.ndarray:
    """(T, L) matrix: values[t], values[parent(t)], ... padded with the root's
    value — the layer-by-layer comparison chain of topologyNodeLessFunc
    (``network_topology_solver.go:334``)."""
    cols = []
    cur = jnp.arange(topo.num_topo)
    for _ in range(int(topo.node_path.shape[1])):
        cols.append(values[cur])
        cur = topo.topo_parent[cur]
    return jnp.stack(cols, axis=1)


def rank_candidates(
    topo: TopologyArrays,
    candidates: jnp.ndarray,
    topo_slots: jnp.ndarray,
    topo_scores: jnp.ndarray,
    topo_existing: jnp.ndarray,
    prefer_lower_slots: bool = True,
) -> jnp.ndarray:
    """Order candidate topology nodes by the reference's lexicographic rule:
    existing peers (desc) up the chain, then offer slots (asc when selecting a
    candidate, desc when filling children) up the chain, then score (desc),
    then id. Returns topo ids sorted best-first (non-candidates last)."""
    ex = _ancestor_chain_keys(topo, topo_existing)
    sl = _ancestor_chain_keys(topo, topo_slots)
    sc = topo_scores
    sign = 1 if prefer_lower_slots else -1
    # lexsort: last key is the primary.
    keys = [jnp.arange(topo.num_topo), -sc]
    num_layers = int(topo.node_path.shape[1])
    for layer in range(num_layers - 1, -1, -1):
        keys.append(sign * sl[:, layer])
    for layer in range(num_layers - 1, -1, -1):
        keys.append(-ex[:, layer])
    keys.append(~candidates)  # candidates first
    return jnp.lexsort(keys)


#: rows of :func:`plan_prep`'s (5, T) output
PREP_SLOTS, PREP_SCORES, PREP_EXISTING, PREP_CAND, PREP_RANKED = range(5)


def plan_prep(
    alloc: jnp.ndarray,
    work: tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray],
    node_valid: jnp.ndarray,
    requests: jnp.ndarray,
    feasible: jnp.ndarray,
    member: jnp.ndarray,
    topo: TopologyArrays,
    desired: jnp.ndarray,
    must_gather_layer: jnp.ndarray,
    layer_multiples: jnp.ndarray,
    node_scores: jnp.ndarray,
    node_existing: jnp.ndarray,
    cfg=None,
) -> jnp.ndarray:
    """The device half of one gang's plan, in one program: the members'
    requests packed to the front, the nodes every member may use, offer
    slots against the round's working accounting ``work`` = (requested,
    usage, agg_usage), tree aggregation, layer multiples, eligibility and
    ranking.  Returns (5, T) int32: rows ``PREP_*`` (the candidate mask as
    0/1), one transfer for the host walk."""
    p = requests.shape[0]
    order = jnp.argsort(~member, stable=True)
    n_members = member.sum()
    gang_requests = jnp.where((jnp.arange(p) < n_members)[:, None],
                              requests[order], 0)
    # per-pod feasibility (affinity etc.) applies to the whole gang: a node
    # any member cannot use offers no slots to the gather plan
    node_ok = node_valid & ~jnp.any(member[:, None] & ~feasible, axis=0)
    requested, usage, agg_usage = work
    slots = _offer_slots(alloc, requested, usage, agg_usage, gang_requests,
                         node_ok, cfg, n_members)
    t_slots, t_scores, t_existing = aggregate_tree(
        topo, slots, node_scores, node_existing)
    t_slots = constrain_multiples(topo, t_slots, layer_multiples)
    cand, _ = eligible_candidates(topo, t_slots, desired, must_gather_layer)
    ranked = rank_candidates(topo, cand, t_slots, t_scores, t_existing)
    return jnp.stack([t_slots, t_scores, t_existing,
                      cand.astype(jnp.int32), ranked.astype(jnp.int32)])


def charge_plan(
    work: tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray],
    requests: jnp.ndarray,
    plan: jnp.ndarray,
    cfg,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """``work`` with one gang's plan taken: each planned pod's request on
    its node's requested row and its estimated usage (``cfg``'s LoadAware
    estimator) on the node's usage rows, as the solve will charge them, so
    the next gang of the round is planned against what this one leaves."""
    from koordinator_tpu.ops import scoring

    requested, usage, agg_usage = work
    on = plan >= 0
    rows = jnp.where(on, plan, 0)
    requested = requested.at[rows].add(jnp.where(on[:, None], requests, 0))
    est = jnp.where(on[:, None], scoring.estimate_pod_usage_by_band(
        requests, cfg.estimator_factors, cfg.estimator_defaults), 0)
    return requested, usage.at[rows].add(est), agg_usage.at[rows].add(est)


@dataclasses.dataclass(frozen=True)
class HostTree:
    """The tree's arrays on the host, with each topology node's children:
    what the distribute walk reads, made once per tree build."""

    parent: np.ndarray
    layer: np.ndarray
    topo_to_node: np.ndarray
    children: dict

    @classmethod
    def of(cls, topo: TopologyArrays) -> "HostTree":
        parent = np.asarray(topo.topo_parent)
        children: dict[int, list[int]] = {}
        for tid, p in enumerate(parent.tolist()):
            if p != tid:
                children.setdefault(p, []).append(tid)
        return cls(parent, np.asarray(topo.topo_layer),
                   np.asarray(topo.topo_to_node), children)


def _distribute_host(
    topo_parent: np.ndarray,
    topo_layer: np.ndarray,
    topo_to_node: np.ndarray,
    slots: np.ndarray,
    scores: np.ndarray,
    existing: np.ndarray,
    root: int,
    desired: int,
    layer_multiples: np.ndarray,
    children: dict,
) -> tuple[list[int], list[int]]:
    """Recursive slot distribution over the chosen subtree
    (``network_topology_solver.go:353 distributeOfferSlot``). Host-side: only
    touches the T-sized tree (``children``: :attr:`HostTree.children`).
    Returns (ordered physical node ids, counts)."""

    def chain(tid):
        out = [tid]
        while topo_parent[out[-1]] != out[-1]:
            out.append(int(topo_parent[out[-1]]))
        return out

    def sort_key(tid):
        ch = chain(tid)
        return (
            tuple(-existing[c] for c in ch),
            tuple(-slots[c] for c in ch),  # fill higher-slot children first
            -scores[tid],
            tid,
        )

    nodes: list[int] = []
    counts: list[int] = []

    def walk(tid, want) -> int:
        layer = int(topo_layer[tid])
        mult = int(layer_multiples[layer]) if layer < len(layer_multiples) else 1
        take = min(int(slots[tid]), want)
        if mult > 1:
            take = (take // mult) * mult
        phys = int(topo_to_node[tid]) if tid < len(topo_to_node) else -1
        if phys >= 0 or tid not in children:
            if phys >= 0 and take > 0:
                nodes.append(phys)
                counts.append(take)
            return take if phys >= 0 else 0
        got = 0
        for child in sorted(children.get(tid, []), key=sort_key):
            got += walk(child, take - got)
            if got >= take:
                break
        return got

    got = walk(root, desired)
    return (nodes, counts) if got >= desired else ([], [])


def layer_multiples_of(req: TopologyRequirements, num_layers: int) -> np.ndarray:
    """(L,) int32 pod-count multiple per layer, 1 where the gang sets none."""
    mults = np.asarray(req.layer_multiples or (), np.int32)
    return np.pad(mults, (0, num_layers - len(mults)), constant_values=1)


def walk_ranked(prep: np.ndarray, host: HostTree, member_idx: np.ndarray,
                desired: int, mults: np.ndarray, capacity: int) -> np.ndarray:
    """The host half of one gang's plan: walk the ranked candidates of
    :func:`plan_prep`'s output until one distributes fully.  Returns the
    (``capacity``,) int32 planned node of each member, all -1 when no
    candidate holds the gang."""
    plan = np.full(capacity, -1, np.int32)
    cand = prep[PREP_CAND].astype(bool)
    if not cand.any():
        return plan
    for tid in prep[PREP_RANKED]:
        if not cand[tid]:
            break
        nodes, counts = _distribute_host(
            host.parent, host.layer, host.topo_to_node, prep[PREP_SLOTS],
            prep[PREP_SCORES], prep[PREP_EXISTING], int(tid), desired, mults,
            host.children)
        if nodes:
            flat = np.repeat(nodes, counts)[: len(member_idx)]
            plan[member_idx[: len(flat)]] = flat
            return plan
    return plan


def _prep_one(state, pods, gang_mask, topo, req, node_scores, node_existing,
              cfg):
    """(member_idx, desired, mults, :func:`plan_prep`'s output) of one gang
    against ``state``."""
    n = state.capacity
    if node_scores is None:
        node_scores = jnp.zeros(n, jnp.int32)
    if node_existing is None:
        node_existing = jnp.zeros(n, jnp.int32)
    gang_mask = np.asarray(gang_mask)
    member_idx = np.flatnonzero(gang_mask)
    desired = req.desired_slots if req.desired_slots > 0 else len(member_idx)
    mults = layer_multiples_of(req, topo.num_layers)
    out = plan_prep(
        state.node_allocatable,
        (state.node_requested, state.node_usage, state.node_agg_usage),
        state.node_valid, pods.requests, pods.feasible_rows(state),
        jnp.asarray(gang_mask), topo, jnp.int32(desired),
        jnp.int32(req.must_gather_layer), jnp.asarray(mults), node_scores,
        node_existing, cfg)
    return member_idx, desired, mults, out


def gang_candidate_prep(
    state: ClusterState,
    pods: PodBatch,
    gang_mask: np.ndarray,
    topo: TopologyArrays,
    req: TopologyRequirements,
    node_scores: jnp.ndarray | None = None,
    node_existing: jnp.ndarray | None = None,
    cfg=None,
):
    """Candidate-prep pipeline shared by BOTH gang planners (the
    baseline :func:`plan_gang_placement` and
    quality/topo_gang.plan_gang_placement_quality): :func:`plan_prep`,
    the whole-gang node feasibility intersection, member-request
    front-packing, offer slots, tree aggregation, multiples and
    eligibility.  One implementation, so a feasibility or multiples fix
    can never land in one planner and silently diverge the other.

    Returns ``(member_idx, desired, mults, t_slots, t_scores,
    t_existing, cand)``; only candidate ORDER and the commit rule
    differ between planners downstream.
    """
    member_idx, desired, mults, out = _prep_one(
        state, pods, gang_mask, topo, req, node_scores, node_existing, cfg)
    return (member_idx, desired, jnp.asarray(mults), out[PREP_SLOTS],
            out[PREP_SCORES], out[PREP_EXISTING], out[PREP_CAND] > 0)


def plan_gang_placement(
    state: ClusterState,
    pods: PodBatch,
    gang_mask: np.ndarray,
    topo: TopologyArrays,
    req: TopologyRequirements,
    node_scores: jnp.ndarray | None = None,
    node_existing: jnp.ndarray | None = None,
    cfg=None,
) -> np.ndarray:
    """Full placement plan for one gang: (P,) int32 planned node per gang pod
    (-1 for non-members / infeasible). Mirrors PlacePods
    (``network_topology_solver.go:53``): the plan is then fed to the solver
    one node at a time (the reference's FindOneNode path).
    """
    member_idx, desired, mults, out = _prep_one(
        state, pods, gang_mask, topo, req, node_scores, node_existing, cfg)
    return walk_ranked(np.asarray(out), HostTree.of(topo), member_idx,
                       desired, mults, pods.capacity)
