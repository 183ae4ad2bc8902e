"""Elastic-quota admission as a device kernel.

The reference's hot-path check (``elasticquota/plugin.go`` PreFilter:
used + podRequest <= runtime at the pod's quota, optionally recursively up the
parent chain — checkQuotaRecursive, plugin.go:256-304) becomes tensor algebra:

- the host flattens the quota tree into an ancestor-chain index matrix
  (Q, D) and headroom tensors, clamping int64 headroom into int32 (a clamped
  headroom only matters when it exceeds any possible pod request, so admission
  decisions are unchanged);
- :func:`quota_admission_mask` then answers a whole pod batch at once, and
  :func:`charge_quota` applies Reserve-time accounting to every ancestor so
  sequential assignment sees quota feedback on device.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

from koordinator_tpu.api.resources import NUM_RESOURCE_DIMS
from koordinator_tpu.quota.tree import UNBOUNDED, QuotaTree

#: int32 headroom clamp; far above any single pod request so clamping cannot
#: flip an admission decision, far below int32 max so Reserve-time subtraction
#: cannot underflow across a batch.
HEADROOM_CLAMP = 2**30


@struct.dataclass
class QuotaDeviceState:
    """Flattened quota tree on device. Q quota rows, D max chain depth."""

    headroom: jax.Array   # (Q, R) int32: runtime - used, clamped
    min_headroom: jax.Array  # (Q, R) int32: min - nonPreemptibleUsed, clamped
    checked: jax.Array    # (Q, R) bool: dims declared in the quota's max
    chain: jax.Array      # (Q, D) int32 ancestor indices (self first), -1 pad
    valid: jax.Array      # (Q,) bool

    @property
    def capacity(self) -> int:
        return self.headroom.shape[0]

    @classmethod
    def from_tree(
        cls, tree: QuotaTree, max_depth: int = 8, capacity: int | None = None
    ) -> tuple["QuotaDeviceState", dict[str, int]]:
        """Flatten; returns (state, name->row index map)."""
        names = sorted(tree.nodes)
        q = len(names)
        cap = capacity if capacity is not None else max(8, 1 << (q - 1).bit_length() if q else 3)
        if cap < q:
            raise ValueError(f"capacity {cap} < {q} quotas in tree")
        index = {n: i for i, n in enumerate(names)}

        headroom = np.zeros((cap, NUM_RESOURCE_DIMS), np.int32)
        min_headroom = np.zeros((cap, NUM_RESOURCE_DIMS), np.int32)
        checked = np.zeros((cap, NUM_RESOURCE_DIMS), bool)
        chain = np.full((cap, max_depth), -1, np.int32)
        valid = np.zeros(cap, bool)

        for name, i in index.items():
            node = tree.nodes[name]
            hr = node.runtime - node.used
            mh = node.min - node.non_preemptible_used
            headroom[i] = np.clip(hr, -HEADROOM_CLAMP, HEADROOM_CLAMP)
            min_headroom[i] = np.clip(mh, -HEADROOM_CLAMP, HEADROOM_CLAMP)
            checked[i] = node.max != UNBOUNDED
            anc = tree.ancestors(name)
            if len(anc) > max_depth:
                raise ValueError(f"quota chain deeper than {max_depth}: {anc}")
            chain[i, : len(anc)] = [index[a] for a in anc]
            valid[i] = True

        state = cls(
            headroom=jnp.asarray(headroom),
            min_headroom=jnp.asarray(min_headroom),
            checked=jnp.asarray(checked),
            chain=jnp.asarray(chain),
            valid=jnp.asarray(valid),
        )
        return state, index


@jax.named_scope("quota_admission")
def quota_admission_mask(
    quota: QuotaDeviceState,
    pod_requests: jnp.ndarray,     # (P, R) int32
    pod_quota_id: jnp.ndarray,     # (P,) int32, -1 = no quota (always admitted)
    non_preemptible: jnp.ndarray | None = None,  # (P,) bool
    check_parents: bool = True,
) -> jnp.ndarray:
    """(P,) bool: pod fits its quota chain's headroom on every checked dim.

    Parity: plugin.go PreFilter — podRequest masked to the quota's declared
    max dims, used+request <= runtime; non-preemptible pods additionally check
    nonPreemptibleUsed+request <= min; EnableCheckParentQuota walks ancestors.
    """
    qid = jnp.maximum(pod_quota_id, 0)
    chain = quota.chain[qid]                       # (P, D)
    depth = chain.shape[1] if check_parents else 1
    chain = chain[:, :depth]
    level_ok = chain >= 0                          # (P, D)
    safe = jnp.maximum(chain, 0)

    headroom = quota.headroom[safe]                # (P, D, R)
    # The reference masks the pod request ONCE by the pod's own quota's
    # declared max dims (quotav1.Mask in PreFilter) and checks those same dims
    # at every ancestor — an ancestor's own max never widens or narrows the
    # checked set.
    checked = quota.checked[qid][:, None, :]       # (P, 1, R)
    req = pod_requests[:, None, :]                 # (P, 1, R)
    fits = (req <= headroom) | ~checked | (req == 0)
    ok = jnp.all(jnp.all(fits, axis=-1) | ~level_ok, axis=-1)  # (P,)

    if non_preemptible is not None:
        own = quota.min_headroom[qid]              # (P, R)
        np_fits = jnp.all(
            (pod_requests <= own) | ~quota.checked[qid] | (pod_requests == 0),
            axis=-1,
        )
        ok = ok & (np_fits | ~non_preemptible)

    # A stale/padded quota row (valid False) must reject, not vacuously admit;
    # only quota_id < 0 ("no quota") bypasses the check entirely.
    ok = ok & quota.valid[qid]
    return ok | (pod_quota_id < 0)


def charge_quota_batch(
    quota: QuotaDeviceState,
    requests: jnp.ndarray,        # (P, R) int32
    quota_ids: jnp.ndarray,       # (P,) int32, -1 = no-op
    mask: jnp.ndarray,            # (P,) bool — which pods actually charge
    non_preemptible: jnp.ndarray, # (P,) bool
    sign: int = 1,
) -> QuotaDeviceState:
    """Reserve/Unreserve accounting for a pod batch in one scatter.

    Subtracts (sign=1) or returns (sign=-1) each masked pod's request from
    every ancestor's headroom; non-preemptible pods additionally consume their
    own quota's min headroom (the reference updates NonPreemptibleUsed
    alongside Used)."""
    qid = jnp.maximum(quota_ids, 0)
    chain = quota.chain[qid]                  # (P, D)
    active = (
        (chain >= 0)
        & (quota_ids >= 0)[:, None]
        & mask[:, None]
        & quota.valid[qid][:, None]
    )
    safe = jnp.maximum(chain, 0)              # (P, D)
    delta = jnp.where(
        active[:, :, None], -sign * requests[:, None, :], 0
    )  # (P, D, R)
    headroom = quota.headroom.at[safe.reshape(-1)].add(
        delta.reshape(-1, requests.shape[-1])
    )
    np_active = (
        mask & (quota_ids >= 0) & non_preemptible & quota.valid[qid]
    )
    min_delta = jnp.where(np_active[:, None], -sign * requests, 0)
    min_headroom = quota.min_headroom.at[qid].add(min_delta)
    return quota.replace(headroom=headroom, min_headroom=min_headroom)


def charge_quota(
    quota: QuotaDeviceState,
    request: jnp.ndarray,    # (R,) int32
    quota_id: jnp.ndarray,   # () int32, -1 = no-op
    sign: int = 1,
    non_preemptible: jnp.ndarray | bool = False,
) -> QuotaDeviceState:
    """Single-pod convenience wrapper over :func:`charge_quota_batch`."""
    return charge_quota_batch(
        quota,
        request[None, :],
        quota_id[None],
        jnp.ones((1,), bool),
        jnp.asarray(non_preemptible)[None],
        sign=sign,
    )
