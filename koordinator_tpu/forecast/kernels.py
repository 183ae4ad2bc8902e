"""Jitted kernels of the forecast plane.

Everything here is device math over the same ``(N, R)`` tensor layout
the solver owns (state/cluster_state.py):

- :func:`predicted_peaks` — the batched percentile over the node
  histogram bank, horizon-extrapolated by the diurnal trend slope, as
  one ``(N, R)`` int32 predicted-peak tensor.  The horizon and growth
  rate ride as DEVICE scalars end to end: a host cast of either inside
  the jitted flow is the jit-host-sync bug class the seeded forecast
  corpus (tools/koordlint/fixtures/forecast) pins.
- :func:`sharded_predicted_peaks` — the explicit shard_map twin over
  the 2-D mesh's nodes axis.  The percentile is per-row elementwise, so
  the program needs no collectives; every spec is explicit
  (mesh-discipline).
- :func:`admission_reserve` — the forecast-headroom term: the part of
  the predicted peak NOT yet visible in observed usage, as an
  ``(N, R)`` reserve the solve charges for the round.
- :func:`forecast_gang_assign` — the SolverKit entry: charge the
  reserve into ``node_requested``, run the standard gang/greedy solve,
  release the reserve from the returned state.  One jitted program, so
  no host-visible intermediate state ever carries the charge and a
  solve failure recovers exactly like today's entries.
- :func:`migration_cost_gate` — the proactive-rebalance move gate over
  the resident cluster-state tensors: a pre-staged migration is allowed
  only while an underutilized destination can absorb the pod on every
  configured dimension WITHOUT crossing its own high threshold
  (sequential capacity feedback, like ``select_victims``).

Empty histograms predict 0 (the sentinel — never NaN); predictions clip
to ``MAX_QUANTITY`` so the int32 invariant every downstream percent and
score kernel relies on survives extrapolation.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from koordinator_tpu.api.resources import NUM_RESOURCE_DIMS, ResourceDim
from koordinator_tpu.parallel.mesh import NODES_AXIS
from koordinator_tpu.prediction.histogram import (
    ExponentialBuckets,
    HistogramBank,
    percentile,
)
from koordinator_tpu.state.cluster_state import MAX_QUANTITY

#: percentiles per dimension, matching the koordlet's per-pod peak
#: predictors (prediction/predictor.py: p95 cpu / p98 memory)
CPU_PERCENTILE = 0.95
MEM_PERCENTILE = 0.98


def _peak_one_dim(weights, total, buckets: ExponentialBuckets, p: float,
                  horizon_s, growth_per_hour, safety_margin_pct: float):
    """(N,) float32 horizon-extrapolated peak of one resource dim.

    ``horizon_s`` / ``growth_per_hour`` are () device scalars; the
    extrapolation is multiplicative (the trend slope arrives as a
    RELATIVE growth rate per hour), clamped to growth — a downward
    trend never shrinks the peak below the histogram's own percentile,
    the conservative direction for admission.
    """
    bank = HistogramBank(weights=weights, total=total,
                         ref_time=jnp.float32(0.0),
                         half_life=jnp.float32(1.0))
    peak = percentile(bank, buckets, p)
    peak = peak * (100.0 + safety_margin_pct) / 100.0
    growth = jnp.maximum(growth_per_hour, 0.0) * (horizon_s / 3600.0)
    return peak * (1.0 + growth)


def predicted_peaks(
    cpu_weights: jax.Array,   # (N, Bc) float32 decayed bucket weights
    cpu_total: jax.Array,     # (N,) float32
    mem_weights: jax.Array,   # (N, Bm) float32
    mem_total: jax.Array,     # (N,) float32
    horizon_s: jax.Array,     # () float32 — device scalar, never host-cast
    growth_per_hour: jax.Array,  # () float32 relative growth rate
    *,
    cpu_buckets: ExponentialBuckets,
    mem_buckets: ExponentialBuckets,
    safety_margin_pct: float = 10.0,
) -> jax.Array:
    """(N, R) int32 predicted peak usage at the horizon.

    Only the prod dims (CPU/MEMORY) carry predictions — the
    overcommitted batch/mid dims are DERIVED from these peaks by the
    colocation formula, not forecast independently.  Empty histograms
    predict 0.
    """
    n = cpu_weights.shape[0]
    cpu = _peak_one_dim(cpu_weights, cpu_total, cpu_buckets, CPU_PERCENTILE,
                        horizon_s, growth_per_hour, safety_margin_pct)
    mem = _peak_one_dim(mem_weights, mem_total, mem_buckets, MEM_PERCENTILE,
                        horizon_s, growth_per_hour, safety_margin_pct)
    out = jnp.zeros((n, NUM_RESOURCE_DIMS), jnp.float32)
    out = out.at[:, ResourceDim.CPU].set(cpu)
    out = out.at[:, ResourceDim.MEMORY].set(mem)
    return jnp.clip(out, 0.0, float(MAX_QUANTITY)).astype(jnp.int32)


def sharded_predicted_peaks(
    mesh,
    cpu_weights: jax.Array,
    cpu_total: jax.Array,
    mem_weights: jax.Array,
    mem_total: jax.Array,
    horizon_s: jax.Array,
    growth_per_hour: jax.Array,
    *,
    cpu_buckets: ExponentialBuckets,
    mem_buckets: ExponentialBuckets,
    safety_margin_pct: float = 10.0,
) -> jax.Array:
    """The explicit shard_map twin of :func:`predicted_peaks`: the bank
    shards its node axis over the mesh's nodes axis (the same placement
    the cluster state pins), the percentile runs per-shard (per-row
    math, no collectives), and the (N, R) result comes back
    node-sharded — bit-identical to the single-device kernel."""
    if cpu_weights.shape[0] % int(mesh.shape[NODES_AXIS]):
        raise ValueError(
            f"bank capacity {cpu_weights.shape[0]} does not divide over "
            f"the {int(mesh.shape[NODES_AXIS])}-way nodes axis")

    def local(cw, ct, mw, mt, h, g):
        return predicted_peaks(
            cw, ct, mw, mt, h, g,
            cpu_buckets=cpu_buckets, mem_buckets=mem_buckets,
            safety_margin_pct=safety_margin_pct)

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(NODES_AXIS), P(NODES_AXIS), P(NODES_AXIS),
                  P(NODES_AXIS), P(), P()),
        out_specs=P(NODES_AXIS))
    return fn(cpu_weights, cpu_total, mem_weights, mem_total,
              horizon_s, growth_per_hour)


# koordlint: shape[predicted: NxR i32 nodes, ret0: NxR i32 nodes]
def admission_reserve(
    predicted: jax.Array,      # (N, R) int32 predicted peaks
    node_usage: jax.Array,     # (N, R) int32 observed usage
    node_valid: jax.Array,     # (N,) bool
) -> jax.Array:
    """(N, R) int32 forecast-headroom reserve: the forecast GROWTH —
    the part of the predicted peak observed usage does not cover yet.
    Charged into ``node_requested`` for the round by
    :func:`forecast_gang_assign`, so filter and score both see the
    node as that much fuller before the LS ramp arrives."""
    grow = jnp.clip(predicted - node_usage, 0, MAX_QUANTITY)
    return jnp.where(node_valid[:, None], grow, 0).astype(jnp.int32)


# koordlint: shape[state: NxR i32 nodes, reserve: NxR i32 nodes]
def forecast_gang_assign(state, reserve, pods, cfg, gangs, quota=None,
                         passes: int = 2, solver: str = "greedy",
                         with_grants: bool = False):
    """``gang_assign`` with the forecast-headroom reserve charged for
    the duration of the solve — the predictive-admission SolverKit
    entry.

    One jitted program: charge -> solve -> release, so the charge never
    escapes into host-visible state (an execution failure recovers
    through the same donation path as the plain entry), and the
    returned state carries exactly the round's placements — quota
    charges and accounting are bit-identical to the unforecast solve
    for any pod both would place."""
    from koordinator_tpu.ops.gang import gang_assign

    charged = state.replace(node_requested=state.node_requested + reserve)
    # ``rest``: with_grants' device grants and scan stats, handed through
    a, new_state, new_quota, *rest = gang_assign(
        charged, pods, cfg, gangs, quota, passes=passes, solver=solver,
        with_grants=with_grants)
    return (a, new_state.replace(
        node_requested=new_state.node_requested - reserve), new_quota,
        *rest)


def reserve_fraction_sums(reserve: jax.Array, state) -> tuple[jax.Array,
                                                              jax.Array]:
    """((R,), (R,)) float32 sums of (reserve, allocatable) over valid
    nodes — the ``forecast_admission_reserved_fraction`` inputs (float32
    accumulation: summed int32 quantities overflow at 10k nodes)."""
    valid = state.node_valid[:, None]
    return (
        jnp.sum(jnp.where(valid, reserve, 0).astype(jnp.float32), axis=0),
        jnp.sum(jnp.where(valid, state.node_allocatable, 0
                          ).astype(jnp.float32), axis=0),
    )


def realized_peak_update(realized: jax.Array, node_usage: jax.Array,
                         node_valid: jax.Array) -> jax.Array:
    """(N, R) int32 running max of observed usage since the last
    refresh — the ground truth the NEXT refresh scores its previous
    prediction against."""
    return jnp.where(node_valid[:, None],
                     jnp.maximum(realized, node_usage), 0)


def forecast_error_sums(predicted: jax.Array, realized: jax.Array,
                        node_valid: jax.Array) -> tuple[jax.Array,
                                                        jax.Array]:
    """((R,), (R,)) float32 sums of |predicted - realized| and realized
    over valid nodes with any realized signal — the
    ``forecast_error_fraction{dim}`` inputs.  Nodes that saw no usage
    in the window contribute to neither sum (a 0/0 must read as "no
    signal", not 100% error)."""
    seen = node_valid[:, None] & (realized > 0)
    err = jnp.abs(predicted - realized)
    return (
        jnp.sum(jnp.where(seen, err, 0).astype(jnp.float32), axis=0),
        jnp.sum(jnp.where(seen, realized, 0).astype(jnp.float32), axis=0),
    )


def migration_cost_gate(
    pod_usage: jax.Array,       # (K, R) int32 candidate pods' usage
    node_usage: jax.Array,      # (N, R) int32 observed node usage
    capacity: jax.Array,        # (N, R) int32 node capacity
    under: jax.Array,           # (N,) bool underutilized destinations
    high_thresholds: jax.Array, # (R,) int32 percent, -1 unconfigured
) -> tuple[jax.Array, jax.Array]:
    """((K,) bool gate, (K,) int32 destination rows) for pre-staged
    migrations.

    A move passes the cost gate only while some underutilized node can
    absorb the pod on EVERY configured dimension without crossing its
    own high threshold; accepted moves charge their destination before
    the next candidate evaluates (sequential capacity feedback — two
    pods cannot both claim the last slot).  Destination is the
    feasible node with the most post-move slack; gate False returns
    destination -1."""
    configured = high_thresholds >= 0
    high_quant = jnp.where(
        configured[None, :],
        capacity * jnp.maximum(high_thresholds, 0)[None, :] // 100,
        jnp.int32(2**30))

    def step(usage, pod):
        room = high_quant - usage                      # (N, R)
        fits = under & jnp.all(
            (~configured[None, :]) | (pod[None, :] <= room), axis=1)
        # slack score: the tightest configured dim's post-move headroom
        slack = jnp.min(jnp.where(configured[None, :], room - pod[None, :],
                                  jnp.int32(2**30)), axis=1)
        ok = jnp.any(fits)
        dest = jnp.argmax(jnp.where(fits, slack, jnp.int32(-2**30)))
        delta = jnp.where(ok, pod, 0)
        usage = usage.at[dest].add(delta)
        return usage, (ok, jnp.where(ok, dest, -1).astype(jnp.int32))

    _, (gate, dest) = jax.lax.scan(step, node_usage, pod_usage)
    return gate, dest
