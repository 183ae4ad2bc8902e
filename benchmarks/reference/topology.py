"""Plain reference of koordinator's network-topology gang placement
(Coscheduling's ``PlacePods`` over a ``ClusterNetworkTopology`` tree built
from node labels), and a round of gangs planned one after another.

int64 numpy and Python loops; imports nothing of the program.  Written from
memory of upstream (``frameworkext/networktopology/tree.go``;
``coscheduling/core/network_topology_solver.go``: ``PlacePods``,
``calculateNodeOfferSlot``, ``evaluateTopologyNode``,
``constrainOfferSlotByPodCountMultiple``, ``searchOfferSlotSatisfiedNodes``,
``distributeOfferSlot``): there is no network and no copy of the source on
this machine.

The steps, for one gang against the free capacity of every node:

1. per node, its offer slots: how many of the gang's members, taken in
   order, fit together on the node's free capacity;
2. the slots summed up the tree, into every domain above the node;
3. bottom-up, a domain's slots are the sum of its children's and rounded
   down to its layer's pod-count multiple (a leaf's own slots too);
4. from the must-gather layer down (the root for "anywhere"), the domains
   whose slots hold the gang and whose parents did, and of them those of
   the deepest layer: the candidates;
5. the candidates ranked by the tighter fit (fewer slots), compared first
   at the domain, then at its parent and up to the root; then by the lower
   topology id;
6. down the first candidate that holds the whole gang, the members are
   handed out: at each domain to its children with the most slots first
   (the same chain, then the lower id), each child given at most what it
   offers, rounded down to its layer's multiple.

A round plans its gangs in turn, in the scheduler's gang order, and charges
each plan (the members' requests on their nodes) before the next gang is
planned.

Departures from the published description, each on purpose:

- upstream ranks a domain that already holds members of the gang first.
  The gangs this reference meets arrive whole in one round, with nothing of
  theirs bound, and the program plans with no such count: neither does this.
- upstream's offer slots run the node's whole filter chain for each added
  pod.  Here a slot is the node's free capacity (allocatable less what is
  requested) alone: the deployment runs LoadAware with no usage thresholds
  (its nodes report no usage, and upstream's filter passes a node without a
  NodeMetric), no selector or taint keeps a pod off a node, and every device
  is healthy and asked for whole, so the node's aggregate GPU row decides
  its devices' fit.
- upstream breaks ties between domains by the nodes' scores.  The program
  plans with a score of 0 everywhere, so ties go to the lower topology id.
- topology ids are handed out in order of first appearance as nodes are
  added (the root is 0), and a domain is keyed by its path from the root;
  upstream keys tree nodes by layer and name.
- the round's gangs are planned in the scheduler's gang order (by gang
  name); upstream plans one gang at a time as its pods reach the head of the
  queue.
"""

from __future__ import annotations

import numpy as np


class Tree:
    """``layers`` layers: the cluster root (0), one per label key, the node
    (last).  Nodes join by their label path; ``None`` where a node has no
    label for a layer: it hangs from the deepest layer it has one for."""

    def __init__(self, layers: int):
        self.layers = layers
        self.parent = [0]
        self.layer = [0]
        self.index: dict[tuple, int] = {(): 0}
        #: row -> (ancestor ids at the layers it has, its leaf id)
        self.ancestors: dict[int, list[int]] = {}
        self.leaf_row: dict[int, int] = {}

    def add(self, row: int, path: list) -> None:
        if len(path) != self.layers - 1 or path[-1] is None:
            raise ValueError(f"bad path {path!r}")
        parent, prefix, ids = 0, (), [0]
        for depth, name in enumerate(path, start=1):
            if name is None:
                continue
            prefix = prefix + ((depth, name),)
            tid = self.index.get(prefix)
            if tid is None:
                tid = len(self.parent)
                self.index[prefix] = tid
                self.parent.append(parent)
                self.layer.append(depth)
            ids.append(tid)
            parent = tid
        self.ancestors[row] = ids
        self.leaf_row[ids[-1]] = row

    def finish(self) -> None:
        """Arrays and child lists, once every node is in."""
        self.parent_a = np.asarray(self.parent, np.int64)
        self.layer_a = np.asarray(self.layer, np.int64)
        self.children: dict[int, list[int]] = {}
        for tid, p in enumerate(self.parent):
            if p != tid:
                self.children.setdefault(p, []).append(tid)
        rows = sorted(self.ancestors)
        self.rows = np.asarray(rows, np.int64)
        self.pairs = np.asarray(
            [(r, a) for r in rows for a in self.ancestors[r]],
            np.int64).reshape(-1, 2)

    def chain(self, tid: int) -> list[int]:
        """``tid`` and its ancestors up to the root, padded with the root
        to the tree's depth."""
        out = [tid]
        while self.parent[out[-1]] != out[-1]:
            out.append(self.parent[out[-1]])
        return out + [0] * (self.layers - len(out))


def offer_slots(free: np.ndarray, members: np.ndarray,
                valid: np.ndarray) -> np.ndarray:
    """(N,) for each node the count of members, taken in order, that fit on
    it together (one member at a time, over all nodes at once)."""
    slots = np.zeros(len(free), np.int64)
    used = np.zeros_like(free, dtype=np.int64)
    still = valid.copy()
    for request in members.astype(np.int64):
        used = used + request
        still &= np.all(used <= free, axis=1)
        if not still.any():
            break
        slots += still
    return slots


def constrained(tree: Tree, node_slots: np.ndarray,
                mults: np.ndarray) -> np.ndarray:
    """(T,) every domain's slots: leaves from their node, each domain the
    sum of its children, every value rounded down to its layer's multiple."""
    t = len(tree.parent)
    slots = np.zeros(t, np.int64)
    for leaf, row in tree.leaf_row.items():
        slots[leaf] = node_slots[row]
    for layer in range(tree.layers - 1, -1, -1):
        at = np.flatnonzero(tree.layer_a == layer)
        if layer < tree.layers - 1:
            kids = np.flatnonzero((tree.layer_a[tree.parent_a] == layer)
                                  & (tree.parent_a != np.arange(t)))
            summed = np.bincount(tree.parent_a[kids], weights=slots[kids],
                                 minlength=t).astype(np.int64)
            slots[at] = summed[at]
        m = max(int(mults[layer]), 1)
        slots[at] = slots[at] // m * m
    return slots


def candidates(tree: Tree, slots: np.ndarray, desired: int,
               gather: int) -> list[int]:
    """The deepest layer's domains reachable from the must-gather layer
    through domains that hold ``desired``, best first."""
    start = max(gather, 0)
    sat = slots >= desired
    ok = np.zeros(len(slots), bool)
    for layer in range(start, tree.layers):
        at = np.flatnonzero(tree.layer_a == layer)
        if layer == start:
            ok[at] = sat[at]
        else:
            ok[at] = sat[at] & ok[tree.parent_a[at]]
    if not ok.any():
        return []
    deepest = int(tree.layer_a[ok].max())
    found = np.flatnonzero(ok & (tree.layer_a == deepest)).tolist()
    return sorted(found, key=lambda tid: (
        tuple(int(slots[c]) for c in tree.chain(tid)), tid))


def distribute(tree: Tree, slots: np.ndarray, root: int, want: int,
               mults: np.ndarray) -> list[tuple[int, int]]:
    """(node row, members) down ``root``; empty when it cannot give all."""
    out: list[tuple[int, int]] = []

    def walk(tid: int, want: int) -> int:
        m = max(int(mults[tree.layer[tid]]), 1)
        take = min(int(slots[tid]), want) // m * m
        if tid in tree.leaf_row:
            if take > 0:
                out.append((tree.leaf_row[tid], take))
            return take
        got = 0
        kids = sorted(tree.children.get(tid, []), key=lambda c: (
            tuple(-int(slots[a]) for a in tree.chain(c)), c))
        for kid in kids:
            if got >= take:
                break
            got += walk(kid, take - got)
        return got

    return out if walk(root, want) >= want else []


def place_pods(tree: Tree, free: np.ndarray, valid: np.ndarray,
               members: np.ndarray, desired: int, gather: int,
               mults: np.ndarray) -> list[tuple[int, int]]:
    """One gang's plan: (node row, members) in hand-out order, or []."""
    slots = constrained(tree, offer_slots(free, members, valid), mults)
    for tid in candidates(tree, slots, desired, gather):
        plan = distribute(tree, slots, tid, desired, mults)
        if plan:
            return plan
    return []


def plan_round(tree: Tree, free: np.ndarray, valid: np.ndarray,
               gangs: list[dict]) -> list[list[tuple[int, int]]]:
    """Plans of a round's gangs, in the order given, each charged into
    ``free`` (changed in place) before the next.  A gang is ``{"members":
    (M, R) requests in member order, "desired", "gather", "mults"}``."""
    plans = []
    for gang in gangs:
        members = np.asarray(gang["members"], np.int64)
        plan = place_pods(tree, free, valid, members, gang["desired"],
                          gang["gather"], np.asarray(gang["mults"]))
        taken = 0
        for row, count in plan:
            free[row] -= members[taken:taken + count].sum(axis=0)
            taken += count
        plans.append(plan)
    return plans
