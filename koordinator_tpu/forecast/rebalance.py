"""Proactive rebalance: pre-staged, reservation-first migrations off
nodes FORECAST to run hot.

Today's descheduler reacts: LowNodeLoad classifies observed usage, and
a node must be observed overutilized for ``anomaly_rounds`` consecutive
rounds before anything moves (descheduler/lownodeload.py) — by which
time the LS spike landed and the eviction is an emergency.  This driver
runs the SAME classification kernels over the forecast usage tensor
(``max(observed, predicted)`` — a forecast never makes a node look
emptier than it is), so the anomaly counters start ticking BEFORE the
spike and the moves happen while they are still cheap:

- victims come from :func:`~koordinator_tpu.descheduler.lownodeload.
  select_victims` over the forecast tensor (priority-ordered, budgeted
  against the underutilized pool — the exact semantics the reactive
  path has, just on predicted state);
- every move passes the migration-cost gate
  (:func:`~koordinator_tpu.forecast.kernels.migration_cost_gate`) over
  the resident cluster-state tensors: an underutilized destination must
  absorb the pod on every configured dim without crossing its own high
  threshold, with sequential capacity feedback;
- gated moves become reservation-first
  :class:`~koordinator_tpu.descheduler.migration.MigrationJob`\\ s: the
  controller reserves replacement capacity (``reserve_many``) before any
  eviction fires, so a pre-staged pod is never left homeless.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax.numpy as jnp
import numpy as np

from koordinator_tpu import metrics
from koordinator_tpu.descheduler.lownodeload import (
    LowNodeLoadArgs,
    classify_nodes,
    select_victims,
    update_anomaly_counters,
)
from koordinator_tpu.descheduler.migration import (
    MigrationController,
    MigrationJob,
)
from koordinator_tpu.forecast import kernels


@dataclasses.dataclass
class StagedMove:
    """One pre-staged migration the tick produced."""

    pod: str
    node: str
    dest: str
    job: MigrationJob


class ProactiveRebalancer:
    """Forecast-classified LowNodeLoad + cost-gated pre-staging.

    ``pods_fn()`` returns the victim universe as parallel arrays
    ``(names, pod_node (P,), pod_usage (P, R), pod_priority (P,),
    pod_evictable (P,))`` — the same shape ``select_victims`` takes;
    ``node_name_fn(row)`` resolves destination rows.  The controller's
    ``reserve_many``/``evict_fn`` stay the caller's seams (a real stack
    wires the scheduler's reservation API; the A/B harness books its
    simulated capacity).
    """

    def __init__(self, plane,
                 controller: MigrationController,
                 pods_fn: Callable[[], tuple],
                 node_name_fn: Callable[[int], Optional[str]],
                 args: LowNodeLoadArgs | None = None,
                 prestage_cap: int = 64):
        self.plane = plane
        self.controller = controller
        self.pods_fn = pods_fn
        self.node_name_fn = node_name_fn
        self.args = args if args is not None else LowNodeLoadArgs.default()
        #: at most this many moves stage per tick — proactive rebalance
        #: is a trickle ahead of the ramp, not a mass drain
        self.prestage_cap = prestage_cap
        self._anomaly = None
        self._staged: set[str] = set()
        self.ticks = 0
        self.staged_total = 0

    def tick(self, usage, capacity, node_valid,
             forecast=None) -> list[StagedMove]:
        """One proactive round over the (N, R) node tensors.  Returns
        the moves staged this tick (already submitted to the
        controller, which the caller reconciles on its own cadence).

        ``forecast`` overrides the classified tensor for callers whose
        plane predicts only a COMPONENT of node usage (the A/B harness
        forecasts LS and adds observed BE on top); the default is the
        plane's ``max(observed, predicted)``."""
        self.ticks += 1
        usage = jnp.asarray(usage)
        capacity = jnp.asarray(capacity)
        node_valid = jnp.asarray(node_valid)
        if forecast is None:
            forecast = self.plane.forecast_usage(usage)

        n = forecast.shape[0]
        if self._anomaly is None or self._anomaly.shape[0] != n:
            self._anomaly = jnp.zeros((n,), jnp.int32)
        under, over = classify_nodes(forecast, capacity, node_valid,
                                     self.args)
        self._anomaly = update_anomaly_counters(self._anomaly, over)

        names, pod_node, pod_usage, pod_priority, pod_evictable = (
            self.pods_fn())
        if len(names) == 0:
            return []
        # pods already staged must not stage again while their job runs
        evictable = np.asarray(pod_evictable, bool).copy()
        for i, name in enumerate(names):
            if name in self._staged:
                evictable[i] = False
        victims = np.asarray(select_victims(
            forecast, capacity, node_valid,
            jnp.asarray(pod_node), jnp.asarray(pod_usage),
            jnp.asarray(pod_priority), jnp.asarray(evictable),
            self._anomaly, self.args))
        rows = np.flatnonzero(victims)[: self.prestage_cap]
        if len(rows) == 0:
            return []

        # cost gate over the OBSERVED state: destinations must absorb
        # the pod today, not just in the forecast (a move into a node
        # that is presently full trades one hot node for another).
        # Candidates pad to the prestage cap so the sequential scan
        # compiles once per cap, not once per candidate count.
        padded = np.zeros((self.prestage_cap, np.asarray(pod_usage).shape[1]),
                          np.int32)
        padded[: len(rows)] = np.asarray(pod_usage)[rows]
        gate, dest = kernels.migration_cost_gate(
            jnp.asarray(padded), usage, capacity, under,
            self.args.high_thresholds)
        gate, dest = np.asarray(gate), np.asarray(dest)

        moves: list[StagedMove] = []
        pod_node_np = np.asarray(pod_node)
        for j, i in enumerate(rows):
            if not gate[j]:
                continue
            pod = names[int(i)]
            src = self.node_name_fn(int(pod_node_np[i])) or str(
                int(pod_node_np[i]))
            dst = self.node_name_fn(int(dest[j])) or str(int(dest[j]))
            job = MigrationJob(
                name=f"forecast-{pod}-t{self.ticks}",
                pod=pod, node=src, priority=int(
                    np.asarray(pod_priority)[i]))
            try:
                self.controller.submit(job)
            except ValueError:
                continue      # an identically-named job is still live
            self._staged.add(pod)
            self.staged_total += 1
            metrics.forecast_evictions_prestaged.inc()
            moves.append(StagedMove(pod=pod, node=src, dest=dst, job=job))
        return moves

    def release(self, pod: str) -> None:
        """A staged pod finished migrating (or died): it may stage
        again in a later tick."""
        self._staged.discard(pod)
