"""Cluster state as fixed-capacity padded tensors.

Design notes (SURVEY.md section 7 "hard parts"):

- **Fixed capacity + masks.** Nodes and pods come and go; XLA wants static
  shapes. State tensors are allocated at a capacity (a power-of-two bucket) and
  carry validity masks. Growing past capacity re-allocates at the next bucket —
  a recompile, amortized to O(log N) recompiles over cluster life.
- **Delta scatter updates.** The host keeps an index map (name -> row); informer
  deltas become ``tensor.at[rows].set(values)`` scatters of only changed rows,
  not full-state uploads. This is the double-buffer-friendly update path that
  keeps host->device traffic proportional to churn.
- **Integer exactness.** Resource math is int32 in canonical units
  (see api/resources.py) to match the reference's int64 milli-unit math.

Reference-parity mapping:
  node_allocatable  <- Node.status.allocatable (scheduler NodeInfo snapshot)
  node_requested    <- sum of scheduled pods' requests (NodeInfo.Requested)
  node_usage        <- NodeMetric.status.nodeMetric.nodeUsage (slo/v1alpha1, nodemetric_types.go:131)
  node_agg_usage    <- NodeMetric AggregatedUsage percentile (nodemetric_types.go:50)
  node_prod_usage   <- prod-pool usage (loadaware prod-usage mode)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

from koordinator_tpu.api.resources import NUM_RESOURCE_DIMS
from koordinator_tpu.ops import introspection as insp
from koordinator_tpu.ops.deviceshare import DeviceState


#: Per-dimension quantity bound: integer score/percentage math multiplies by
#: 100, so quantities must stay below 2^31/100 to avoid int32 overflow
#: (api/resources.py documents the unit scaling that keeps real nodes within
#: this: 21.4M mcores / 21.4M MiB ~ 20 TiB memory per node).
MAX_QUANTITY = (2**31 - 1) // 100


def _check_bounds(a: np.ndarray | None, what: str) -> None:
    if a is not None and np.asarray(a).size and np.asarray(a).max() > MAX_QUANTITY:
        raise ValueError(
            f"{what} exceeds MAX_QUANTITY={MAX_QUANTITY}; rescale units "
            "(see api/resources.py)"
        )


def _bucket(n: int, minimum: int = 64) -> int:
    """Smallest power-of-two capacity >= n (recompile bucketing)."""
    cap = minimum
    while cap < n:
        cap *= 2
    return cap


#: donating row scatter: XLA aliases the output to the input buffer, so a
#: flush-sized (N, R) tensor is updated in place instead of reallocated
_row_set_donating = jax.jit(
    lambda cur, rows, value: cur.at[rows].set(value), donate_argnums=(0,))

#: the one device op of the request accounting: a dense (N, R) delta
#: added.  Its shape hangs on the capacity alone, never on how many rows
#: changed, so it compiles once a capacity bucket; instrumented like the
#: solver's entry points, so a compile inside a steady window shows in
#: solver_recompiles_total{fn="fold_requested"}.  What it donates is the
#: DELTA, which nobody else holds: the sum takes over that buffer, so a
#: fold allocates nothing, and the pre-fold ``node_requested`` stays alive
#: for whoever still holds the pre-fold state (a round's handle, a
#: preemption pass), as it did when each call was a device op of its own
_requested_fold = insp.instrument(
    jax.jit(lambda cur, delta: cur + delta, donate_argnums=(1,)),
    "fold_requested")


@struct.dataclass
class ClusterState:
    """Per-node tensors, shape (N, R) / (N,). N is the padded node capacity."""

    node_allocatable: jax.Array  # (N, R) int32
    node_requested: jax.Array    # (N, R) int32 — requests of pods bound to the node
    node_usage: jax.Array        # (N, R) int32 — latest real usage (NodeMetric)
    node_agg_usage: jax.Array    # (N, R) int32 — aggregated percentile usage (e.g. p95)
    node_prod_usage: jax.Array   # (N, R) int32 — usage by prod-band pods only
    node_valid: jax.Array        # (N,)  bool
    #: (N,) int32 label/taint equivalence-class id per node: nodes with the
    #: same scheduling-relevant labels+taints share a class, so pod
    #: feasibility factors into a (P, C) selector mask + this map instead of
    #: a dense (P, N) tensor (C ≪ N; the reference walks nodeSelector/taints
    #: per (pod, node) — the class map is the vectorized equivalent).
    node_class: jax.Array
    #: the GPU device plane in the same node rows ((N, D, 2) free and
    #: total, (N, D) valid / healthy / group), or None while no node has
    #: reported a device inventory.  None is part of the pytree's
    #: STRUCTURE: a cluster without devices traces the programs it
    #: always traced, and one with devices gets DeviceShare's Filter and
    #: Reserve inside the same solve (ops/deviceshare.py).
    devices: DeviceState | None = None

    @property
    def capacity(self) -> int:
        return self.node_allocatable.shape[0]

    @property
    def free(self) -> jax.Array:
        """(N, R) request-free capacity; 0 for invalid nodes."""
        free = self.node_allocatable - self.node_requested
        return jnp.where(self.node_valid[:, None], free, 0)

    @classmethod
    def zeros(cls, capacity: int, dims: int = NUM_RESOURCE_DIMS) -> "ClusterState":
        # one DISTINCT buffer per field: the donating flush consumes
        # fields independently, so aliased zeros would die together
        def z():
            return jnp.zeros((capacity, dims), dtype=jnp.int32)

        return cls(
            node_allocatable=z(),
            node_requested=z(),
            node_usage=z(),
            node_agg_usage=z(),
            node_prod_usage=z(),
            node_valid=jnp.zeros((capacity,), dtype=bool),
            node_class=jnp.zeros((capacity,), dtype=jnp.int32),
        )

    @classmethod
    def from_arrays(
        cls,
        allocatable: np.ndarray,
        requested: np.ndarray | None = None,
        usage: np.ndarray | None = None,
        agg_usage: np.ndarray | None = None,
        prod_usage: np.ndarray | None = None,
        capacity: int | None = None,
        node_class: np.ndarray | None = None,
    ) -> "ClusterState":
        """Build padded device state from (n, R) host arrays of n real nodes."""
        n, dims = allocatable.shape
        cap = capacity if capacity is not None else _bucket(n)
        _check_bounds(allocatable, "node allocatable")

        def pad(a):
            out = np.zeros((cap, dims), dtype=np.int32)
            if a is not None:
                out[:n] = a
            return jnp.asarray(out)

        valid = np.zeros(cap, dtype=bool)
        valid[:n] = True
        nclass = np.zeros(cap, dtype=np.int32)
        if node_class is not None:
            nclass[:n] = node_class
        return cls(
            node_allocatable=pad(allocatable),
            node_requested=pad(requested),
            node_usage=pad(usage),
            node_agg_usage=pad(agg_usage if agg_usage is not None else usage),
            node_prod_usage=pad(prod_usage if prod_usage is not None else usage),
            node_valid=jnp.asarray(valid),
            node_class=jnp.asarray(nclass),
        )

    def scatter_update(self, rows: jax.Array, donate: bool = False,
                       **updates: jax.Array) -> "ClusterState":
        """Apply a delta: replace the given rows of the named tensors.

        ``rows`` is (K,) int32; each update value is (K, R) (or (K,) for masks).
        Only the changed rows travel host->device.

        ``donate=True`` routes each row-set through a donating jit so the
        (N, R) tensor is updated in place instead of reallocated — for
        callers that OWN the state exclusively (the snapshot's flush):
        the pre-update buffers are dead after the call and any stale
        reference to them errors loudly.
        """
        new = {}
        setter = _row_set_donating if donate else (
            lambda cur, r, v: cur.at[r].set(v))
        for name, value in updates.items():
            cur = getattr(self, name)
            new[name] = setter(cur, rows, value)
        return self.replace(**new)

    def gather_rows(self, rows: jax.Array,
                    row_valid: jax.Array | None = None) -> "ClusterState":
        """Sub-state of the given node rows (shape (K, R) / (K,)): the
        dirty-column view the incremental candidate refresh scores
        against.  ``row_valid`` additionally masks padded entries of a
        bucketed ``rows`` vector so they score as invalid nodes."""
        valid = self.node_valid[rows]
        if row_valid is not None:
            valid = valid & row_valid
        return ClusterState(
            node_allocatable=self.node_allocatable[rows],
            node_requested=self.node_requested[rows],
            node_usage=self.node_usage[rows],
            node_agg_usage=self.node_agg_usage[rows],
            node_prod_usage=self.node_prod_usage[rows],
            node_valid=valid,
            node_class=self.node_class[rows],
            devices=(None if self.devices is None else
                     jax.tree.map(lambda a: a[rows], self.devices)),
        )

    def fold_requested(self, delta: np.ndarray) -> "ClusterState":
        """``node_requested + delta`` in ONE device op: the whole of the
        host-accumulated Reserve / Unreserve accounting since the last
        fold (``ClusterSnapshot``).  ``delta`` is a host (N, R) int32
        array the caller hands over for good (the transfer may read it
        after this returns); it is placed like ``node_requested``, so a
        node-axis-sharded state stays sharded and the add needs no
        collective.  ``self`` is left as it was: the op donates the
        placed delta, not the state."""
        placed = jax.device_put(delta, self.node_requested.sharding)
        return self.replace(node_requested=_requested_fold(
            self.node_requested, placed))


@struct.dataclass
class PodBatch:
    """A batch of pending pods, shape (P, R) / (P,). P is padded pod capacity.

    Placement constraints (nodeSelector / affinity / taints+tolerations) come
    in one of two representations:

    - **factored** (the default, the scale path): ``selector_mask`` is a
      (P, C) bool over node equivalence classes and the node→class map lives
      in ``ClusterState.node_class``; feasibility expands lazily on device as
      ``selector_mask[:, node_class]``, so host work and transfer are
      O(P·C + N), never O(P·N).
    - **dense**: an explicit host-computed (P, N) ``feasible`` mask for
      callers that need per-(pod, node) edits (scheduling hints, topology
      pinning, tests).

    Exactly one of the two is set; use :meth:`feasible_rows` /
    :meth:`feasible_row` instead of touching either field.
    """

    requests: jax.Array    # (P, R) int32
    priority: jax.Array    # (P,) int32 — koordinator priority value
    qos: jax.Array         # (P,) int8  — QoSClass codes
    gang_id: jax.Array     # (P,) int32 — gang index, -1 = not in a gang
    quota_id: jax.Array    # (P,) int32 — elastic-quota index, -1 = none
    non_preemptible: jax.Array  # (P,) bool — checks/consumes quota min
    valid: jax.Array       # (P,) bool
    #: (P,) int32 tie-break rotation identity: the candidate ranking's
    #: per-pod rotation (ops/batch_assign._ranked_scores) derives from
    #: this, NOT from the pod's batch row, so a pod keeps its candidate
    #: set when the queue around it churns (the incremental candidate
    #: cache depends on that stability).  Defaults to the batch row
    #: index; the scheduler assigns a stable id per pod name.
    rot_id: jax.Array
    feasible: jax.Array | None       # (P, N) bool dense mask, or None
    selector_mask: jax.Array | None  # (P, C) bool class mask, or None

    @property
    def capacity(self) -> int:
        return self.requests.shape[0]

    def feasible_rows(self, state: "ClusterState") -> jax.Array:
        """(P, N) feasibility, expanding the factored form on device.

        A node whose class id is outside this batch's selector-mask width
        (a class registered after the batch was built) is INFEASIBLE for
        every pod — failing safe (the pod retries next round against a
        rebuilt batch) rather than silently inheriting another class's mask.
        """
        if self.feasible is not None:
            return self.feasible
        c = self.selector_mask.shape[1]
        in_range = state.node_class < c
        nc = jnp.minimum(state.node_class, c - 1)
        return self.selector_mask[:, nc] & in_range[None, :]

    def feasible_row(self, state: "ClusterState", idx) -> jax.Array:
        """(N,) feasibility for one pod (cheap in the factored form)."""
        if self.feasible is not None:
            return self.feasible[idx]
        c = self.selector_mask.shape[1]
        in_range = state.node_class < c
        nc = jnp.minimum(state.node_class, c - 1)
        return self.selector_mask[idx][nc] & in_range

    def compact(
        self, keep: np.ndarray, min_capacity: int = 32
    ) -> tuple["PodBatch", np.ndarray]:
        """(small_batch, kept_indices): gather the ``keep`` rows into a new
        batch padded to a power-of-two capacity (power-of-two bucketing keeps
        the jit cache bounded).  Padded rows are invalid.

        The scale rationale: a follow-up solve over a handful of leftover
        pods (the scheduler's exact rescue pass) must not pay the full
        O(capacity) scan of the original 50k-row batch.
        """
        idx = np.flatnonzero(np.asarray(keep))
        cap = max(min_capacity, 1 << (max(len(idx), 1) - 1).bit_length())
        pad = np.zeros(cap, np.int32)
        pad[: len(idx)] = idx
        gidx = jnp.asarray(pad)
        valid_pad = np.zeros(cap, bool)
        valid_pad[: len(idx)] = True

        # every PodBatch field is per-pod along axis 0, so gather the whole
        # pytree (None constraint fields drop out of the map)
        small = jax.tree.map(lambda a: jnp.take(a, gidx, axis=0), self)
        return small.replace(valid=small.valid & jnp.asarray(valid_pad)), idx

    @classmethod
    def build(
        cls,
        requests: np.ndarray,
        priority: np.ndarray | None = None,
        qos: np.ndarray | None = None,
        gang_id: np.ndarray | None = None,
        quota_id: np.ndarray | None = None,
        non_preemptible: np.ndarray | None = None,
        feasible: np.ndarray | None = None,
        selector_mask: np.ndarray | None = None,
        node_capacity: int = 64,
        class_capacity: int = 1,
        capacity: int | None = None,
        rot_id: np.ndarray | None = None,
    ) -> "PodBatch":
        p, dims = requests.shape
        cap = capacity if capacity is not None else _bucket(p)
        _check_bounds(requests, "pod requests")

        req = np.zeros((cap, dims), dtype=np.int32)
        req[:p] = requests

        def pad1(a, fill, dtype):
            out = np.full(cap, fill, dtype=dtype)
            if a is not None:
                out[:p] = a
            return jnp.asarray(out)

        if feasible is not None:
            feas = np.zeros((cap, node_capacity), dtype=bool)
            feas[:p, : feasible.shape[1]] = feasible
            feas_arr, sel_arr = jnp.asarray(feas), None
        else:
            c_cap = class_capacity
            sel = np.zeros((cap, c_cap), dtype=bool)
            if selector_mask is not None:
                sel[:p, : selector_mask.shape[1]] = selector_mask
            else:
                sel[:p] = True  # unconstrained pods allow every class
            feas_arr, sel_arr = None, jnp.asarray(sel)

        valid = np.zeros(cap, dtype=bool)
        valid[:p] = True

        # rotation identity defaults to the batch row (the pre-cache
        # behavior); padded rows keep their row index (inert: invalid)
        rot = np.arange(cap, dtype=np.int32)
        if rot_id is not None:
            rot[:p] = rot_id

        return cls(
            requests=jnp.asarray(req),
            priority=pad1(priority, 0, np.int32),
            qos=pad1(qos, 0, np.int8),
            gang_id=pad1(gang_id, -1, np.int32),
            quota_id=pad1(quota_id, -1, np.int32),
            non_preemptible=pad1(non_preemptible, False, bool),
            valid=jnp.asarray(valid),
            rot_id=jnp.asarray(rot),
            feasible=feas_arr,
            selector_mask=sel_arr,
        )
