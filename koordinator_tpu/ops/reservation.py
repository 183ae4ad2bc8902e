"""Reservation-aware scheduling kernels.

TPU-native equivalent of the reference's reservation plugin
(pkg/scheduler/plugins/reservation/: plugin.go, transformer.go restore path,
scoring.go, nominator.go). The reference models a Reservation as a "reserve
pod" that occupies node resources (apis/scheduling/v1alpha1/
reservation_types.go:250); pods matching the reservation's owners may then
allocate out of the reserved-but-unallocated remainder. Here the whole
reservation set is a fixed-capacity tensor struct and the restore/fit/score
logic is batched over (pods x reservations) / (pods x nodes).

Accounting invariant: when a reservation becomes Available on a node, the host
charges its full reserved vector to that node's ``node_requested`` (the
reserve-pod trick, snapshot.reserve). So plain pods already cannot see the
reserved capacity; these kernels hand the *remaining* (reserved - allocated)
back to owner-matched pods only.

Allocate policies (reservation_types.go:81-99):
- Aligned (default): an owner pod allocates from the reservation first and any
  spill comes from ordinary node free capacity.
- Restricted: for every resource named in the reservation, the pod's request
  must fit entirely within the reservation's remainder; unreserved dims spill
  to node free.
AllocateOnce (reservation_types.go:60-64): first successful owner consumes the
whole reservation.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

from koordinator_tpu.api.resources import NUM_RESOURCE_DIMS
from koordinator_tpu.ops import scoring
from koordinator_tpu.state.cluster_state import ClusterState, PodBatch, _bucket


@struct.dataclass
class ReservationSet:
    """Fixed-capacity padded reservation tensors (V rows)."""

    valid: jax.Array          # (V,) bool — row holds an Available reservation
    node_idx: jax.Array       # (V,) int32 — node the reservation sits on, -1 none
    reserved: jax.Array       # (V, R) int32 — total reserved (reservation allocatable)
    allocated: jax.Array      # (V, R) int32 — currently allocated to owner pods
    allocate_once: jax.Array  # (V,) bool
    restricted: jax.Array     # (V,) bool — Restricted vs Aligned policy

    @property
    def capacity(self) -> int:
        return self.valid.shape[0]

    @property
    def active(self) -> jax.Array:
        """(V,) bool — row holds a valid reservation PLACED on a node;
        the one definition of usability shared by remaining /
        reservation_fit / allocate_from_reservation."""
        return self.valid & (self.node_idx >= 0)

    @property
    def remaining(self) -> jax.Array:
        """(V, R) reserved-but-unallocated, zero for invalid/unplaced rows."""
        return jnp.where(self.active[:, None],
                         self.reserved - self.allocated, 0)

    @classmethod
    def zeros(cls, capacity: int = 16, dims: int = NUM_RESOURCE_DIMS) -> "ReservationSet":
        return cls(
            valid=jnp.zeros(capacity, bool),
            node_idx=jnp.full(capacity, -1, jnp.int32),
            reserved=jnp.zeros((capacity, dims), jnp.int32),
            allocated=jnp.zeros((capacity, dims), jnp.int32),
            allocate_once=jnp.zeros(capacity, bool),
            restricted=jnp.zeros(capacity, bool),
        )

    @classmethod
    def build(
        cls,
        reserved: np.ndarray,           # (V, R)
        node_idx: np.ndarray,           # (V,)
        allocated: np.ndarray | None = None,
        allocate_once: np.ndarray | None = None,
        restricted: np.ndarray | None = None,
        capacity: int | None = None,
    ) -> "ReservationSet":
        n = len(reserved)
        cap = capacity or _bucket(max(n, 1), minimum=16)
        dims = reserved.shape[1] if n else NUM_RESOURCE_DIMS

        def pad2(a):
            out = np.zeros((cap, dims), np.int32)
            out[:n] = a
            return jnp.asarray(out)

        def pad1(a, fill, dtype):
            out = np.full(cap, fill, dtype)
            if a is not None:
                out[:n] = a
            return jnp.asarray(out)

        valid = np.zeros(cap, bool)
        valid[:n] = True
        return cls(
            valid=jnp.asarray(valid),
            node_idx=pad1(np.asarray(node_idx, np.int32), -1, np.int32),
            reserved=pad2(reserved),
            allocated=pad2(allocated if allocated is not None else np.zeros_like(reserved)),
            allocate_once=pad1(allocate_once, False, bool),
            restricted=pad1(restricted, False, bool),
        )


def reservation_fit(
    rsv: ReservationSet,
    node_free: jnp.ndarray,    # (N, R) free WITHOUT reservation remainders
    requests: jnp.ndarray,     # (P, R)
    match: jnp.ndarray,        # (P, V) owner-matcher result (host-computed)
) -> jnp.ndarray:
    """(P, V) bool — pod p could allocate through reservation v on its node.

    Mirrors plugin.go's per-reservation fit during Filter with the restore
    transformer applied (transformer.go), per allocate policy.
    """
    rows = jnp.clip(rsv.node_idx, 0)
    free_at = node_free[rows]                       # (V, R)
    rem = rsv.remaining                             # (V, R)
    # Exhausted rows (e.g. consumed allocate-once) are no longer a reservation
    # anyone can allocate through — without this they'd keep the score boost.
    active = rsv.active & jnp.any(rem > 0, axis=-1)
    req = requests[:, None, :]                      # (P, 1, R)

    # req == 0 dims must not exclude (allocatable can shrink below what is
    # already scheduled, leaving free negative in an unrequested dim — same
    # escape as filtering.fit_mask).
    unrequested = req == 0
    aligned_ok = jnp.all((req <= (rem + free_at)[None]) | unrequested, axis=-1)
    dim_reserved = rsv.reserved > 0                 # (V, R)
    restricted_ok = jnp.all(
        jnp.where(dim_reserved[None], req <= rem[None], req <= free_at[None])
        | unrequested,
        axis=-1,
    )
    fits = jnp.where(rsv.restricted[None, :], restricted_ok, aligned_ok)
    return fits & match & active[None, :]


def reservation_node_mask(
    fits: jnp.ndarray,         # (P, V)
    rsv: ReservationSet,
    n_nodes: int,
) -> jnp.ndarray:
    """(P, N) bool — node has at least one fitting matched reservation."""
    onehot = (
        jax.nn.one_hot(jnp.clip(rsv.node_idx, 0), n_nodes, dtype=jnp.int32)
        * (rsv.node_idx >= 0)[:, None]
    )                                               # (V, N)
    return (fits.astype(jnp.int32) @ onehot) > 0


def nominate_reservation(
    fits: jnp.ndarray,         # (P, V)
    rsv: ReservationSet,
    node: jnp.ndarray,         # (P,) chosen node per pod
) -> jnp.ndarray:
    """(P,) int32 — the reservation each pod allocates through, -1 for none.

    Among fitting matched reservations on the chosen node, prefer the one with
    the smallest total remainder (best-fit, keeps big reservations intact —
    the nominator.go preference order reduced to a tensor argmin).
    """
    on_node = fits & (rsv.node_idx[None, :] == node[:, None]) & (node[:, None] >= 0)
    total_rem = jnp.sum(rsv.remaining, axis=-1)     # (V,)
    keyed = jnp.where(on_node, total_rem[None, :], jnp.iinfo(jnp.int32).max)
    best = jnp.argmin(keyed, axis=-1)
    has = jnp.any(on_node, axis=-1)
    return jnp.where(has, best, -1).astype(jnp.int32)


def allocate_from_reservation(
    rsv: ReservationSet,
    r_idx: jnp.ndarray,        # () int32, -1 = no reservation
    request: jnp.ndarray,      # (R,)
) -> tuple[ReservationSet, jnp.ndarray]:
    """Charge one pod's allocation to a reservation row.

    Returns (new_rsv, spill): spill is the part of the request NOT covered by
    the reservation remainder (to be charged to the node). AllocateOnce rows
    are consumed entirely (allocated := reserved).
    """
    use = r_idx >= 0
    row = jnp.clip(r_idx, 0)
    rem = rsv.remaining[row]
    take = jnp.where(use, jnp.minimum(request, rem), 0)
    spill = jnp.where(use, request - take, request)
    # consume-whole only applies to an ACTIVE row: an invalid or
    # unplaced reservation has nothing to give (take is already 0 via
    # remaining), and marking it fully allocated would mutate state a
    # caller never drew from (found by the randomized ledger sweep —
    # unreachable through nominate_reservation, which only returns
    # on-node rows, but a direct caller must not trip it)
    consume_all = use & rsv.active[row] & rsv.allocate_once[row]
    new_alloc_row = jnp.where(
        consume_all, rsv.reserved[row], rsv.allocated[row] + take
    )
    new_allocated = rsv.allocated.at[row].set(
        jnp.where(use, new_alloc_row, rsv.allocated[row])
    )
    return rsv.replace(allocated=new_allocated), spill


def score_pods_with_reservations(
    state: ClusterState,
    pods: PodBatch,
    cfg,
    rsv: ReservationSet,
    match: jnp.ndarray,        # (P, V)
    boost: int = 10_000,
):
    """Batched Filter+Score with reservation restore.

    Returns (scores, feasible, fits): feasibility is extended to nodes
    reachable only through a matched reservation, and such nodes get a score
    boost (ReservationScorePlugin semantics: prefer consuming reservations).
    """
    from koordinator_tpu.ops.assignment import _threshold_mask, score_pods

    scores, feasible = score_pods(state, pods, cfg)
    fits = reservation_fit(rsv, state.free, pods.requests, match)
    via_rsv = reservation_node_mask(fits, rsv, state.capacity)
    # The restore path extends *fit*, not the LoadAware usage-threshold filter:
    # an overloaded node stays infeasible even for owner pods (load_aware.go
    # Filter runs regardless of reservation restore).
    pod_est = scoring.estimate_pod_usage_by_band(
        pods.requests, cfg.estimator_factors, cfg.estimator_defaults
    )
    via_rsv = (
        via_rsv
        & _threshold_mask(cfg, state.node_usage, state.node_agg_usage,
                          state.node_allocatable, pod_est)
        & pods.feasible_rows(state)
        & state.node_valid[None, :]
        & pods.valid[:, None]
    )
    feasible = feasible | via_rsv
    scores = scores + jnp.where(via_rsv, boost, 0)
    return scores, feasible, fits


def reservation_greedy_assign(
    state: ClusterState,
    pods: PodBatch,
    cfg,
    rsv: ReservationSet,
    match: jnp.ndarray,        # (P, V)
    quota=None,
    boost: int = 10_000,
):
    """Sequential assignment with reservation-first accounting.

    Like assignment.greedy_assign but each step: (1) extends feasibility with
    matched reservations, (2) prefers reserved nodes, (3) charges the chosen
    reservation's remainder first and only the spill to node_requested
    (Reserve semantics of plugin.go Reserve + nominator).

    Returns (assignments, rsv_choice, new_state, new_rsv, new_quota,
    stats): the last is the scan's :class:`~koordinator_tpu.ops.assignment.
    ScanStats`.
    """
    from koordinator_tpu.ops.assignment import _greedy_scan, keep_devices

    # device feasibility joins the scan; the grants are the commit's
    # (the pre-pass binds one pod at a time through ``_commit_bind``)
    a, choice, new_state, new_rsv, new_quota, _, stats = _greedy_scan(
        state, pods, cfg, quota=quota, rsv=rsv, match=match, rsv_boost=boost
    )
    return a, choice, keep_devices(new_state, state), new_rsv, new_quota, stats
