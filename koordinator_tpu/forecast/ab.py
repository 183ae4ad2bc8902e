"""Reactive-vs-predictive A/B: two stacks, one seeded diurnal trace.

The forecast plane's proof harness (the "Predictive Autoscaler"
methodology from PAPERS.md): generate ONE deterministic per-node
diurnal LS-usage trace from a seed, replay it through two control
stacks that differ ONLY in what they act on —

- **reactive**: the colocation formula sees observed HP usage, and the
  only defense against a hot node is the emergency eviction that fires
  AFTER the threshold is crossed (today's behavior);
- **predictive**: the same formula takes the forecast plane's predicted
  peaks (BE capacity shrinks before the ramp), and the proactive
  rebalancer pre-stages reservation-first migrations off nodes FORECAST
  to cross the high threshold —

and score both arms over identical enforcement: SLO-breach minutes
(node-ticks spent above the high threshold), reactive evictions
(emergency kills at crossings), BE occupancy (the colocation win the
whole exercise must not silently destroy), and the predictive arm's
forecast error (predicted vs realized peak).

Everything is seeded and tensorized on the repo's own kernels: the
batch formula is ``manager/noderesource.batch_allocatable``, victim
selection is ``descheduler/lownodeload.select_victims`` over the
forecast tensor, migrations run through the reservation-first
``MigrationController``, and the horizon follows the diurnal trend
slope via ``trend.fit_slope``.  ``tools/soak_report.py --forecast``
prints the scorecard and exits GREEN only when the predictive arm is
no worse on breaches and evictions.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from koordinator_tpu.api.resources import NUM_RESOURCE_DIMS, ResourceDim
from koordinator_tpu.descheduler.lownodeload import LowNodeLoadArgs
from koordinator_tpu.descheduler.migration import (
    ArbitrationLimits,
    MigrationController,
)
from koordinator_tpu.forecast.plane import ForecastPlane
from koordinator_tpu.forecast.rebalance import ProactiveRebalancer
from koordinator_tpu.manager import noderesource as formula
from koordinator_tpu.trend import fit_slope

#: padded victim-universe capacity: shape-stable select_victims scans
_POD_CAP = 256


@dataclasses.dataclass(frozen=True)
class ABConfig:
    """One A/B run's knobs — the seed expands everything."""

    seed: int = 0
    nodes: int = 16
    #: diurnal periods replayed (>= 2: the plane learns the first ramp,
    #: the arms diverge on the later ones)
    periods: int = 3
    period_s: float = 480.0
    tick_s: float = 24.0
    node_cpu_milli: int = 16_000
    node_memory_mib: int = 65_536
    be_pod_cpu_milli: int = 1_000
    be_pod_memory_mib: int = 512
    #: BE pods the placement loop admits per node (migrations may stack
    #: more): finite BE demand — a cluster where BE greedily fills
    #: every node to the reclaim line has no underutilized pool for
    #: rebalance to move anything INTO
    be_max_pods_per_node: int = 2
    #: per-node LS base / diurnal amplitude, as capacity fractions.
    #: The fleet is heterogeneous — half the nodes are SPIKY (full
    #: diurnal swing; base + amp stays under the high threshold, so
    #: breaches come from LS + BE, never LS alone) and half are FLAT
    #: (near-constant LS: the underutilized pool proactive rebalance
    #: migrates into)
    base_frac: tuple = (0.20, 0.26)
    amp_frac: tuple = (0.32, 0.38)
    flat_amp_frac: tuple = (0.03, 0.07)
    flat_fraction: float = 0.5
    noise_frac: float = 0.01
    #: LowNodeLoad thresholds (percent of capacity) for enforcement and
    #: the proactive classification
    low_threshold_pct: int = 45
    high_threshold_pct: int = 65
    #: consecutive forecast-overutilized ticks before pre-staging
    anomaly_rounds: int = 2
    #: plane knobs
    half_life_s: float = 240.0
    base_horizon_s: float = 120.0
    refresh_interval_s: float = 40.0

    @property
    def ticks(self) -> int:
        return int(self.periods * self.period_s / self.tick_s)

    @property
    def high_quant(self) -> int:
        return self.node_cpu_milli * self.high_threshold_pct // 100


def generate_ls_trace(cfg: ABConfig) -> np.ndarray:
    """(T, N) int32 per-node LS cpu usage (mcores): a phase-staggered
    diurnal sinusoid plus seeded noise — the SAME array feeds both
    arms, the replay-seed discipline loadgen established."""
    rng = np.random.RandomState(cfg.seed)
    n, t = cfg.nodes, cfg.ticks
    base = rng.uniform(*cfg.base_frac, size=n)
    amp = rng.uniform(*cfg.amp_frac, size=n)
    flat_amp = rng.uniform(*cfg.flat_amp_frac, size=n)
    flat = np.arange(n) < int(round(n * cfg.flat_fraction))
    amp = np.where(flat, flat_amp, amp)
    phase = rng.uniform(0.0, cfg.period_s, size=n)
    times = np.arange(t)[:, None] * cfg.tick_s          # (T, 1)
    ramp = 0.5 * (1.0 + np.sin(
        2.0 * math.pi * (times - phase[None, :]) / cfg.period_s))
    frac = base[None, :] + amp[None, :] * ramp
    frac = frac + rng.normal(0.0, cfg.noise_frac, size=(t, n))
    return np.clip(frac * cfg.node_cpu_milli, 0,
                   cfg.node_cpu_milli).astype(np.int32)


class _Arm:
    """One control stack (reactive or predictive) over the shared
    trace.  All mutable state is per-arm; the trace is read-only."""

    def __init__(self, cfg: ABConfig, predictive: bool):
        self.cfg = cfg
        self.predictive = predictive
        n = cfg.nodes
        self.capacity = np.zeros((n, NUM_RESOURCE_DIMS), np.int32)
        self.capacity[:, ResourceDim.CPU] = cfg.node_cpu_milli
        self.capacity[:, ResourceDim.MEMORY] = cfg.node_memory_mib
        self.valid = np.ones(n, bool)
        #: BE registry: pod name -> node row (usage == request, cpu dim)
        self.be_pods: dict[str, int] = {}
        self._be_seq = 0
        # scorecard accumulators
        self.breach_node_ticks = 0
        self.reactive_evictions = 0
        self.be_pod_ticks = 0
        self.prestaged = 0
        self.migrated = 0
        # the batched colocation formula, compiled once per arm
        self._strategy = formula.ColocationStrategy.default()
        self._batch_fn = jax.jit(formula.batch_allocatable)

        self.plane = None
        self.rebalancer = None
        self.controller = None
        self._move_dest: dict[str, int] = {}
        self._growth_samples: list[tuple[float, float]] = []
        if predictive:
            self.plane = ForecastPlane(
                n, half_life_s=cfg.half_life_s,
                base_horizon_s=cfg.base_horizon_s,
                refresh_interval_s=cfg.refresh_interval_s)
            args = LowNodeLoadArgs.default()
            args = args.replace(
                low_thresholds=args.low_thresholds.at[
                    ResourceDim.CPU].set(cfg.low_threshold_pct),
                high_thresholds=args.high_thresholds.at[
                    ResourceDim.CPU].set(cfg.high_threshold_pct),
                anomaly_rounds=jnp.int32(cfg.anomaly_rounds))
            self.controller = MigrationController(
                limits=ArbitrationLimits(max_migrating_per_node=4,
                                         max_migrating_per_namespace=256),
                reserve_many=self._reserve_many, evict_fn=self._evict)
            self.rebalancer = ProactiveRebalancer(
                self.plane, self.controller,
                pods_fn=self._victim_universe,
                node_name_fn=lambda row: f"n{row}",
                args=args)

    # -- BE bookkeeping ------------------------------------------------------

    def be_used(self) -> np.ndarray:
        used = np.zeros(self.cfg.nodes, np.int64)
        for node in self.be_pods.values():
            used[node] += self.cfg.be_pod_cpu_milli
        return used

    def _victim_universe(self):
        names = list(self.be_pods)[:_POD_CAP]
        pod_node = np.full(_POD_CAP, -1, np.int32)
        pod_usage = np.zeros((_POD_CAP, NUM_RESOURCE_DIMS), np.int32)
        priority = np.zeros(_POD_CAP, np.int32)
        evictable = np.zeros(_POD_CAP, bool)
        for i, name in enumerate(names):
            pod_node[i] = self.be_pods[name]
            pod_usage[i, ResourceDim.CPU] = self.cfg.be_pod_cpu_milli
            pod_usage[i, ResourceDim.MEMORY] = self.cfg.be_pod_memory_mib
            evictable[i] = True
        return names, pod_node, pod_usage, priority, evictable

    # -- migration seams (reservation-first) ---------------------------------

    def _reserve_many(self, jobs) -> dict:
        """A reservation for every job whose staged destination still
        has room for one BE pod under the high threshold (nothing moves
        before the evictions, so one reading of the books serves all)."""
        be_used = self.be_used()
        out = {}
        for job in jobs:
            dest = self._move_dest.get(job.name)
            room = (-1 if dest is None else
                    self.cfg.high_quant - self._ls_now[dest]
                    - int(be_used[dest]))
            # None: never staged, or the destination filled up since
            out[job.name] = (f"rsv-{job.name}"
                             if room >= self.cfg.be_pod_cpu_milli else None)
        return out

    def _evict(self, job) -> bool:
        dest = self._move_dest.pop(job.name, None)
        if job.pod in self.be_pods and dest is not None:
            self.be_pods[job.pod] = dest
            self.migrated += 1
        if self.rebalancer is not None:
            self.rebalancer.release(job.pod)
        return True

    # -- one control tick ----------------------------------------------------

    def tick(self, t_idx: int, ls_row: np.ndarray) -> None:
        cfg = self.cfg
        n = cfg.nodes
        now = t_idx * cfg.tick_s
        self._ls_now = ls_row
        usage = np.zeros((n, NUM_RESOURCE_DIMS), np.int32)
        usage[:, ResourceDim.CPU] = ls_row

        hp_used_cpu = ls_row.astype(np.int64)
        if self.predictive:
            self.plane.observe(usage, self.valid, now=now)
            self.plane.maybe_refresh(
                now=now, growth_per_hour=self._growth(now, ls_row))
            peaks = self.plane.predicted_host()
            if peaks is not None:
                # predictive colocation: the batch solve takes the
                # PREDICTED peak (never below the observation)
                hp_used_cpu = np.maximum(
                    hp_used_cpu, peaks[:, ResourceDim.CPU].astype(np.int64))

        # -- colocation: batch allocatable from (observed | predicted) peaks
        zeros = jnp.zeros(n, jnp.int32)
        batch_cpu, _ = self._batch_fn(
            jnp.asarray(self.capacity[:, ResourceDim.CPU]),
            jnp.asarray(self.capacity[:, ResourceDim.MEMORY]),
            zeros, zeros, zeros, zeros,
            jnp.asarray(np.minimum(hp_used_cpu, 2**30).astype(np.int32)),
            zeros, zeros, zeros, zeros, zeros,
            self._strategy)
        batch_cpu = np.asarray(batch_cpu)

        # -- BE placement: fill the advertised batch capacity, up to
        # the finite per-node BE demand
        be_used = self.be_used()
        be_count = np.zeros(n, np.int64)
        for node in self.be_pods.values():
            be_count[node] += 1
        for node in range(n):
            while (be_count[node] < cfg.be_max_pods_per_node
                   and be_used[node] + cfg.be_pod_cpu_milli
                   <= int(batch_cpu[node])
                   and len(self.be_pods) < _POD_CAP):
                name = f"be-{self._be_seq}"
                self._be_seq += 1
                self.be_pods[name] = node
                be_used[node] += cfg.be_pod_cpu_milli
                be_count[node] += 1

        # -- proactive rebalance (predictive arm only): classify the
        # forecast total (BE rides observed; LS rides the prediction)
        if self.predictive and self.plane.ready:
            total = usage.copy()
            total[:, ResourceDim.CPU] += be_used.astype(np.int32)
            peaks = self.plane.predicted_host()
            forecast = total.copy()
            forecast[:, ResourceDim.CPU] = (
                be_used + np.maximum(ls_row.astype(np.int64),
                                     peaks[:, ResourceDim.CPU])
            ).clip(0, 2**30).astype(np.int32)
            moves = self.rebalancer.tick(
                total, self.capacity, self.valid,
                forecast=jnp.asarray(forecast))
            for move in moves:
                self._move_dest[move.job.name] = int(move.dest[1:])
            self.prestaged += len(moves)
            self.controller.reconcile()
            be_used = self.be_used()

        # -- enforcement (identical in both arms): a node over the high
        # threshold accrues breach time and emergency-evicts BE pods
        high = cfg.high_quant
        for node in range(n):
            total_cpu = int(ls_row[node]) + int(be_used[node])
            if total_cpu <= high:
                continue
            self.breach_node_ticks += 1
            victims = [p for p, r in self.be_pods.items() if r == node]
            while total_cpu > high and victims:
                victim = victims.pop()
                del self.be_pods[victim]
                if self.rebalancer is not None:
                    self.rebalancer.release(victim)
                total_cpu -= cfg.be_pod_cpu_milli
                self.reactive_evictions += 1
        self.be_pod_ticks += len(self.be_pods)

    def _growth(self, now: float, ls_row: np.ndarray) -> float:
        """Relative cluster-LS growth per hour from trend.fit_slope over
        the recent window — the horizon policy's input."""
        mean = float(ls_row.mean())
        self._growth_samples.append((now, mean))
        window = [s for s in self._growth_samples
                  if now - s[0] <= 4 * self.cfg.refresh_interval_s]
        self._growth_samples = window
        fit = fit_slope([s[0] for s in window], [s[1] for s in window])
        if fit is None or fit.mean <= 0:
            return 0.0
        return fit.slope * 3600.0 / fit.mean

    def scorecard(self) -> dict:
        cfg = self.cfg
        doc = {
            "arm": "predictive" if self.predictive else "reactive",
            "slo_breach_minutes": round(
                self.breach_node_ticks * cfg.tick_s / 60.0, 3),
            "reactive_evictions": self.reactive_evictions,
            "be_pod_ticks": self.be_pod_ticks,
            "prestaged_migrations": self.prestaged,
            "migrations_completed": self.migrated,
        }
        if self.plane is not None:
            doc["forecast_error_fraction"] = {
                k: round(v, 4) for k, v in self.plane.error_fraction.items()}
            doc["horizon_s"] = self.plane.horizon_s
            doc["refreshes"] = self.plane.refreshes
        return doc


def run_ab(cfg: ABConfig | None = None) -> dict:
    """Replay one seeded diurnal trace through both arms and score
    them.  Deterministic: the same config always yields the same
    scorecard (asserted in tests/test_forecast.py)."""
    cfg = cfg or ABConfig()
    trace = generate_ls_trace(cfg)
    reactive = _Arm(cfg, predictive=False)
    predictive = _Arm(cfg, predictive=True)
    for t in range(cfg.ticks):
        reactive.tick(t, trace[t])
        predictive.tick(t, trace[t])
    r, p = reactive.scorecard(), predictive.scorecard()
    return {
        "seed": cfg.seed,
        "nodes": cfg.nodes,
        "ticks": cfg.ticks,
        "period_s": cfg.period_s,
        "reactive": r,
        "predictive": p,
        # GREEN bar: the predictive arm may not be WORSE on either
        # operational metric (soak_report --forecast exits on this)
        "predictive_no_worse": (
            p["slo_breach_minutes"] <= r["slo_breach_minutes"]
            and p["reactive_evictions"] <= r["reactive_evictions"]),
        "predictive_strictly_better": (
            p["slo_breach_minutes"] < r["slo_breach_minutes"]
            and p["reactive_evictions"] < r["reactive_evictions"]),
    }
