"""LowNodeLoad: classify nodes by real utilization, pick eviction victims.

Semantics from ``pkg/descheduler/framework/plugins/loadaware``:

- classifyNodes (utilization_util.go:239): a node is *underutilized* when
  every configured resource sits below its low threshold, *overutilized* when
  any resource exceeds its high threshold (thresholds are percentages of node
  capacity; NodeMetric usage, not requests).
- deviation thresholds (low_node_load.go:314 newThresholds with
  UseDeviationThresholds): low/high become mean(usage%) -/+ the configured
  deviation, clamped to [0, 100].
- victim selection (utilization_util.go:308 evictPodsFromSourceNodes): the
  budget is the sum over underutilized nodes of (high-threshold capacity -
  usage); pods move off overutilized nodes — sorted cheapest-first — only
  while their node stays above the high threshold and budget remains.
- anomaly gating (low_node_load.go:286 filterRealAbnormalNodes): a node must
  be observed overutilized in several consecutive rounds before eviction;
  tracked here as a per-node counter tensor.

All kernels take the (N, R) usage/capacity tensors already resident for
scheduling — the descheduler reads the same cluster state (BASELINE.json north
star).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

from koordinator_tpu.api.resources import NUM_RESOURCE_DIMS


@struct.dataclass
class LowNodeLoadArgs:
    """LowNodeLoadArgs (descheduler apis/config): thresholds are int32
    percentages; -1 = resource not configured."""

    low_thresholds: jax.Array   # (R,) int32
    high_thresholds: jax.Array  # (R,) int32
    use_deviation: jax.Array    # () bool
    anomaly_rounds: jax.Array   # () int32 — consecutive rounds before evicting

    @classmethod
    def default(cls) -> "LowNodeLoadArgs":
        from koordinator_tpu.api.resources import ResourceDim

        low = jnp.full(NUM_RESOURCE_DIMS, -1, jnp.int32)
        high = jnp.full(NUM_RESOURCE_DIMS, -1, jnp.int32)
        low = low.at[ResourceDim.CPU].set(45).at[ResourceDim.MEMORY].set(60)
        high = high.at[ResourceDim.CPU].set(65).at[ResourceDim.MEMORY].set(80)
        return cls(
            low_thresholds=low,
            high_thresholds=high,
            use_deviation=jnp.asarray(False),
            anomaly_rounds=jnp.int32(3),
        )


def usage_percent(usage: jnp.ndarray, capacity: jnp.ndarray) -> jnp.ndarray:
    """(N, R) usage percentage of capacity; 0 where capacity is 0."""
    return jnp.where(capacity > 0, usage * 100 // jnp.maximum(capacity, 1), 0)


def effective_thresholds(
    args: LowNodeLoadArgs,
    usage_pct: jnp.ndarray,   # (N, R)
    node_valid: jnp.ndarray,  # (N,)
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(low, high) per resource; deviation mode recenters on the pool mean."""
    configured = args.low_thresholds >= 0
    n = jnp.maximum(jnp.sum(node_valid), 1)
    mean = jnp.sum(jnp.where(node_valid[:, None], usage_pct, 0), axis=0) // n
    dev_low = jnp.clip(mean - jnp.maximum(args.low_thresholds, 0), 0, 100)
    dev_high = jnp.clip(mean + jnp.maximum(args.high_thresholds, 0), 0, 100)
    low = jnp.where(args.use_deviation, dev_low, args.low_thresholds)
    high = jnp.where(args.use_deviation, dev_high, args.high_thresholds)
    return (
        jnp.where(configured, low, -1),
        jnp.where(configured, high, -1),
    )


def _classify(pct, low, high, node_valid):
    configured = low >= 0
    under = jnp.all((pct < low) | ~configured, axis=-1) & node_valid
    over = jnp.any(configured & (pct > high), axis=-1) & node_valid
    return under, over


def _high_quantity(capacity, high, unconfigured_fill):
    """capacity * high% for configured dims; fill elsewhere."""
    return jnp.where(high >= 0, capacity * jnp.maximum(high, 0) // 100,
                     unconfigured_fill)


def classify_nodes(
    usage: jnp.ndarray,      # (N, R)
    capacity: jnp.ndarray,   # (N, R)
    node_valid: jnp.ndarray, # (N,)
    args: LowNodeLoadArgs,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(underutilized, overutilized) boolean masks, each (N,)."""
    pct = usage_percent(usage, capacity)
    low, high = effective_thresholds(args, pct, node_valid)
    return _classify(pct, low, high, node_valid)


def update_anomaly_counters(
    counters: jnp.ndarray,  # (N,) int32 consecutive-overutilized rounds
    over: jnp.ndarray,      # (N,) bool this round
) -> jnp.ndarray:
    """filterRealAbnormalNodes counter: increment while over, reset when not."""
    return jnp.where(over, counters + 1, 0)


def eviction_budget(
    usage: jnp.ndarray,
    capacity: jnp.ndarray,
    under: jnp.ndarray,
    high: jnp.ndarray,
) -> jnp.ndarray:
    """(R,) total head-room on underutilized nodes:
    sum(high% * capacity - usage), clamped at 0 per node
    (targetAvailableUsage, utilization_util.go:468)."""
    high_quant = _high_quantity(capacity, high, 0)
    room = jnp.maximum(high_quant - usage, 0)
    return jnp.sum(jnp.where(under[:, None] & (high >= 0), room, 0), axis=0)


def _node_half(usage, capacity, node_valid, args):
    """What victim selection needs of the nodes, before the anomaly gate:
    (overutilized (N,) bool, pool budget (R,), high thresholds (R,), high
    quantity (N, R))."""
    pct = usage_percent(usage, capacity)
    low, high = effective_thresholds(args, pct, node_valid)
    under, over = _classify(pct, low, high, node_valid)
    budget = eviction_budget(usage, capacity, under, high)
    high_quant = _high_quantity(capacity, high, jnp.int32(2**30))
    return over, budget, high, high_quant


def _walk_cheapest_first(usage, budget, high, high_quant, abnormal,
                         pod_node, pod_usage, pod_priority, pod_evictable):
    """(P,) bool victim mask over the pods given, cheapest (lowest
    priority, then smallest cpu usage) first, one at a time: a pod goes
    while its node stays above its high quantity on some configured dim
    and the pool's head-room covers it on every one."""
    p = pod_node.shape[0]
    order = jnp.lexsort((pod_usage[:, 0], pod_priority))

    def step(carry, idx):
        node_usage, budget = carry
        node = pod_node[idx]
        safe = jnp.maximum(node, 0)
        candidate = (
            (node >= 0)
            & pod_evictable[idx]
            & abnormal[safe]
            # node still above high threshold on some configured dim
            & jnp.any((high >= 0) & (node_usage[safe] > high_quant[safe]))
            # pool head-room covers this pod on every configured dim
            & jnp.all((high < 0) | (pod_usage[idx] <= budget))
        )
        delta = jnp.where(candidate, pod_usage[idx], 0)
        node_usage = node_usage.at[safe].add(-delta)
        budget = budget - delta
        return (node_usage, budget), candidate

    (_, _), victims_in_order = jax.lax.scan(step, (usage, budget), order)
    return jnp.zeros(p, bool).at[order].set(victims_in_order)


def select_victims(
    usage: jnp.ndarray,        # (N, R) node usage
    capacity: jnp.ndarray,     # (N, R)
    node_valid: jnp.ndarray,   # (N,)
    pod_node: jnp.ndarray,     # (P,) int32 — node each pod runs on, -1 none
    pod_usage: jnp.ndarray,    # (P, R) — per-pod usage
    pod_priority: jnp.ndarray, # (P,) int32
    pod_evictable: jnp.ndarray,# (P,) bool — passed the eviction filters (PDB,
                               #   owner kind, QoS policy...) computed host-side
    anomaly_counters: jnp.ndarray,  # (N,) int32
    args: LowNodeLoadArgs,
) -> jnp.ndarray:
    """(P,) bool victim mask.

    Evicts lowest-priority pods first from anomalous overutilized nodes, while
    (a) the node remains above its high threshold and (b) the underutilized
    pool still has head-room for the pod (balancePods/evictPods semantics).

    Traceable, and one scan step per pod given: a caller with the whole
    cluster's pods in hand goes through :class:`SourceNodeSelector`, which
    walks the pods of abnormal nodes only.
    """
    over, budget, high, high_quant = _node_half(
        usage, capacity, node_valid, args)
    abnormal = over & (anomaly_counters >= args.anomaly_rounds)
    return _walk_cheapest_first(usage, budget, high, high_quant, abnormal,
                                pod_node, pod_usage, pod_priority,
                                pod_evictable)


def _observe(usage, capacity, node_valid, anomaly_counters, args):
    """One round's node half: (counters after this round, abnormal, budget,
    high, high quantity)."""
    with jax.named_scope("desched/classify"):
        over, budget, high, high_quant = _node_half(
            usage, capacity, node_valid, args)
        counters = update_anomaly_counters(anomaly_counters, over)
        abnormal = over & (counters >= args.anomaly_rounds)
        return counters, abnormal, budget, high, high_quant


def _walk_candidates(usage, budget, high, high_quant, abnormal,
                     cand_node, cand_usage, cand_priority, cand_valid):
    with jax.named_scope("desched/select"):
        return _walk_cheapest_first(usage, budget, high, high_quant,
                                    abnormal, cand_node, cand_usage,
                                    cand_priority, cand_valid)


class SourceNodeSelector:
    """Victim selection at the size of the source nodes.

    Same answers as :func:`select_victims` on every input: a pod that is
    not evictable or not on an abnormal node never changes the walk's
    carry, and a stable sort keeps the order of those that stay.  So the
    node half runs on the device, its (N,) abnormal mask comes to the
    host, the pods of abnormal nodes are gathered there and the walk runs
    over them alone, padded to a power-of-two bucket.  The bucket never
    shrinks: a round with fewer candidates than an earlier one reuses the
    earlier program, and only growth compiles.

    Holds the anomaly counters ((N,) int32, on the device) between rounds.
    """

    #: the smallest bucket a walk is padded to
    MIN_BUCKET = 64

    def __init__(self, args: LowNodeLoadArgs):
        from koordinator_tpu.ops import introspection as insp

        self.args = args
        self.bucket = self.MIN_BUCKET
        self.counters = None
        self._observe = insp.instrument(
            jax.jit(_observe), "lownodeload_observe",
            shape_of=lambda a, k: f"N{a[0].shape[0]}")
        self._walk = insp.instrument(
            jax.jit(_walk_candidates), "lownodeload_walk",
            shape_of=lambda a, k: f"C{a[5].shape[0]}xN{a[0].shape[0]}")

    def observe(self, usage, capacity, node_valid):
        """Advance the anomaly counters by this round's classification;
        returns the round's (abnormal (N,) bool on the host, device
        handles for :meth:`walk`)."""
        n = usage.shape[0]
        if self.counters is None or self.counters.shape[0] != n:
            self.counters = jnp.zeros(n, jnp.int32)
        self.counters, abnormal, budget, high, high_quant = self._observe(
            usage, capacity, node_valid, self.counters, self.args)
        return np.asarray(abnormal), (usage, budget, high, high_quant,
                                      abnormal)

    def walk(self, handles, pod_node, pod_usage, pod_priority, candidates):
        """(len(candidates),) bool: which of ``candidates`` (indices into
        the host pod columns, all evictable and on abnormal nodes) are
        victims.  Equally cheap candidates are walked in the order given."""
        c = len(candidates)
        if c == 0:
            return np.zeros(0, bool)
        while self.bucket < c:
            self.bucket *= 2
        b = self.bucket
        node = np.full(b, -1, np.int32)
        node[:c] = pod_node[candidates]
        cand_usage = np.zeros((b, pod_usage.shape[1]), np.int32)
        cand_usage[:c] = pod_usage[candidates]
        priority = np.zeros(b, np.int32)
        priority[:c] = pod_priority[candidates]
        valid = np.zeros(b, bool)
        valid[:c] = True
        usage, budget, high, high_quant, abnormal = handles
        victims = self._walk(usage, budget, high, high_quant, abnormal,
                             jnp.asarray(node), jnp.asarray(cand_usage),
                             jnp.asarray(priority), jnp.asarray(valid))
        return np.asarray(jax.block_until_ready(victims))[:c]
