"""Prometheus-style metrics (reference: ``pkg/scheduler/metrics/``,
``pkg/koordlet/metrics/`` external+internal registries,
``pkg/util/metrics/``, ``pkg/descheduler/metrics/``).

A minimal dependency-free implementation: Counter / Gauge / Histogram with
labels, per-component registries, and the text exposition format, so the
same scrape endpoints and metric names exist for dashboards
(``dashboards/scheduling.json`` equivalents).
"""

from __future__ import annotations

import threading
import time
from typing import Mapping, Optional, Sequence


def _label_key(labels: Mapping[str, str] | None) -> tuple:
    return tuple(sorted((labels or {}).items()))


def _escape_label_value(value: str) -> str:
    """Text-exposition label escaping (the spec's three escapes, in
    this order so the backslash pass can't double-escape the others):
    backslash, double-quote, line feed."""
    return (str(value).replace("\\", "\\\\").replace('"', '\\"')
            .replace("\n", "\\n"))


def _escape_help(text: str) -> str:
    """# HELP line escaping per the text format: backslash and line
    feed (quotes are legal in HELP text)."""
    return str(text).replace("\\", "\\\\").replace("\n", "\\n")


def _render_labels(key: tuple) -> str:
    if not key:
        return ""
    inner = ",".join(f'{k}="{_escape_label_value(v)}"' for k, v in key)
    return "{" + inner + "}"


def _render_exemplar(ex: dict | None) -> str:
    """OpenMetrics exemplar suffix (`` # {labels} value timestamp``);
    empty for classic-format exposition (ex is None)."""
    if not ex:
        return ""
    labels = ",".join(f'{k}="{_escape_label_value(v)}"'
                      for k, v in sorted(ex["labels"].items()))
    return f" # {{{labels}}} {ex['value']:g} {ex['time']:.3f}"


class _Metric:
    kind = "untyped"

    def __init__(self, name: str, help_text: str = ""):
        self.name = name
        self.help = help_text
        self._lock = threading.Lock()

    def _header(self) -> list[str]:
        return [f"# HELP {self.name} {_escape_help(self.help)}",
                f"# TYPE {self.name} {self.kind}"]

    def expose(self) -> str:
        raise NotImplementedError

    def reset_for_tests(self) -> None:
        """Zero the recorded values (keep the registration + help)."""
        raise NotImplementedError


class Counter(_Metric):
    kind = "counter"

    def __init__(self, name: str, help_text: str = ""):
        super().__init__(name, help_text)
        self._values: dict[tuple, float] = {}

    def inc(self, amount: float = 1.0,
            labels: Mapping[str, str] | None = None) -> None:
        key = _label_key(labels)
        with self._lock:
            self._values[key] = self._values.get(key, 0.0) + amount

    def value(self, labels: Mapping[str, str] | None = None) -> float:
        with self._lock:
            return self._values.get(_label_key(labels), 0.0)

    def items(self) -> list[tuple[dict, float]]:
        """Snapshot of (labels, value) per label set (the SLO sampler's
        read surface; also handy for per-label-set test assertions)."""
        with self._lock:
            return [(dict(key), value)
                    for key, value in self._values.items()]

    def expose(self) -> str:
        lines = self._header()
        with self._lock:
            for key, value in sorted(self._values.items()):
                lines.append(f"{self.name}{_render_labels(key)} {value:g}")
        return "\n".join(lines)

    def reset_for_tests(self) -> None:
        with self._lock:
            self._values.clear()


class Gauge(Counter):
    kind = "gauge"

    def set(self, value: float, labels: Mapping[str, str] | None = None) -> None:
        with self._lock:
            self._values[_label_key(labels)] = value


DEFAULT_BUCKETS = (0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0,
                   2.5, 5.0, 10.0)


class Histogram(_Metric):
    kind = "histogram"

    def __init__(self, name: str, help_text: str = "",
                 buckets: Sequence[float] = DEFAULT_BUCKETS):
        super().__init__(name, help_text)
        self.buckets = tuple(sorted(buckets))
        self._counts: dict[tuple, list[int]] = {}
        self._sums: dict[tuple, float] = {}
        self._totals: dict[tuple, int] = {}
        #: latest exemplar per (label key, bucket le): an observation
        #: annotated with e.g. {"trace_id": ...} lands on its SMALLEST
        #: containing bucket, so an outlier's exemplar survives on the
        #: tail bucket instead of being overwritten by every fast round
        #: (the OpenMetrics attachment rule)
        self._exemplars: dict[tuple, dict] = {}

    def observe(self, value: float,
                labels: Mapping[str, str] | None = None,
                exemplar: Mapping[str, str] | None = None) -> None:
        key = _label_key(labels)
        with self._lock:
            counts = self._counts.setdefault(key, [0] * len(self.buckets))
            bucket_le = "+Inf"
            for i, bound in enumerate(self.buckets):
                if value <= bound:
                    counts[i] += 1
                    if bucket_le == "+Inf":
                        bucket_le = f"{bound:g}"
            self._sums[key] = self._sums.get(key, 0.0) + value
            self._totals[key] = self._totals.get(key, 0) + 1
            if exemplar:
                self._exemplars[(key, bucket_le)] = {
                    "labels": dict(exemplar), "value": float(value),
                    "time": time.time(),
                }

    def exemplars(self, labels: Mapping[str, str] | None = None
                  ) -> dict[str, dict]:
        """{bucket le -> {labels, value, time}} for one label set (the
        /debug linkage from latency outliers to trace ids)."""
        key = _label_key(labels)
        with self._lock:
            return {le: dict(ex) for (k, le), ex in self._exemplars.items()
                    if k == key}

    def quantile(self, q: float,
                 labels: Mapping[str, str] | None = None) -> float:
        """Quantile estimate from exposition state with Prometheus-style
        linear interpolation inside the containing bucket (the SLO
        engine's p99 and tests compute from the same math —
        :func:`quantile_from_buckets`).  Observations in the +Inf bucket
        clamp to the highest finite bound; no data returns 0.0."""
        key = _label_key(labels)
        with self._lock:
            counts = self._counts.get(key)
            # copy under the lock: a concurrent observe() mutates the
            # cumulative list bucket by bucket, and a torn read could
            # momentarily look non-cumulative
            counts = list(counts) if counts else None
            total = self._totals.get(key, 0)
        if not counts or total == 0:
            return 0.0
        return quantile_from_buckets(self.buckets, counts, total, q)

    def state(self) -> list[tuple[dict, list[int], int, float]]:
        """Snapshot per label set: (labels, cumulative finite-bucket
        counts, total incl. +Inf, sum) — the public read surface the
        SLO sampler uses instead of reaching into the lock-guarded
        internals."""
        with self._lock:
            return [(dict(key), list(self._counts[key]),
                     self._totals.get(key, 0), self._sums.get(key, 0.0))
                    for key in self._counts]

    def expose(self, openmetrics: bool = False) -> str:
        """Classic text format by default; ``openmetrics=True`` appends
        exemplar suffixes on bucket lines (classic Prometheus parsers
        reject the `` # {...}`` syntax, so it is strictly opt-in)."""
        lines = self._header()
        with self._lock:
            for key in sorted(self._counts):
                counts = self._counts[key]
                for bound, count in zip(self.buckets, counts):
                    le = f"{bound:g}"
                    bucket_key = key + (("le", le),)
                    ex = (_render_exemplar(self._exemplars.get((key, le)))
                          if openmetrics else "")
                    lines.append(
                        f"{self.name}_bucket{_render_labels(bucket_key)} "
                        f"{count}{ex}"
                    )
                inf_key = key + (("le", "+Inf"),)
                ex = (_render_exemplar(self._exemplars.get((key, "+Inf")))
                      if openmetrics else "")
                lines.append(
                    f"{self.name}_bucket{_render_labels(inf_key)} "
                    f"{self._totals[key]}{ex}"
                )
                lines.append(
                    f"{self.name}_sum{_render_labels(key)} {self._sums[key]:g}"
                )
                lines.append(
                    f"{self.name}_count{_render_labels(key)} {self._totals[key]}"
                )
        return "\n".join(lines)

    def reset_for_tests(self) -> None:
        with self._lock:
            self._counts.clear()
            self._sums.clear()
            self._totals.clear()
            self._exemplars.clear()


class Registry:
    def __init__(self, prefix: str = ""):
        self.prefix = prefix
        self._metrics: dict[str, _Metric] = {}
        self._lock = threading.Lock()

    def _full(self, name: str) -> str:
        return f"{self.prefix}_{name}" if self.prefix else name

    def counter(self, name: str, help_text: str = "") -> Counter:
        return self._get_or_create(name, lambda n: Counter(n, help_text), Counter)

    def gauge(self, name: str, help_text: str = "") -> Gauge:
        return self._get_or_create(name, lambda n: Gauge(n, help_text), Gauge)

    def histogram(self, name: str, help_text: str = "",
                  buckets: Sequence[float] = DEFAULT_BUCKETS) -> Histogram:
        return self._get_or_create(
            name, lambda n: Histogram(n, help_text, buckets), Histogram
        )

    def _get_or_create(self, name: str, factory, expected_type):
        full = self._full(name)
        with self._lock:
            metric = self._metrics.get(full)
            if metric is None:
                metric = self._metrics[full] = factory(full)
            elif not isinstance(metric, expected_type):
                raise ValueError(f"metric {full} already registered as "
                                 f"{type(metric).__name__}")
            return metric

    def items(self) -> list[tuple[str, _Metric]]:
        """Snapshot of (full name, instrument) registrations — the
        public read surface for registry walkers (the SLO sampler, the
        dashboard drift checker) so they stay off the lock-guarded
        internals, mirroring Counter.items/Histogram.state."""
        with self._lock:
            return list(self._metrics.items())

    def expose(self, openmetrics: bool = False) -> str:
        """The /metrics scrape body."""
        with self._lock:
            metrics = list(self._metrics.values())
        return "\n".join(
            m.expose(openmetrics) if isinstance(m, Histogram) else m.expose()
            for m in metrics) + "\n"

    def reset_for_tests(self) -> None:
        """Zero every metric's recorded values WITHOUT dropping the
        registrations (module-level instrument handles stay valid) —
        the per-test isolation hook ``tests/conftest.py`` applies so
        counters stop bleeding across tests within one process."""
        with self._lock:
            metrics = list(self._metrics.values())
        for m in metrics:
            m.reset_for_tests()


# Component registries (the reference's per-component metric packages).
SCHEDULER = Registry("koord_scheduler")
KOORDLET = Registry("koordlet")
MANAGER = Registry("koord_manager")
DESCHEDULER = Registry("koord_descheduler")
TRANSPORT = Registry("koord_transport")
#: process self-telemetry (selftelemetry.py): the same gauges in every
#: binary, labeled {binary=...} — the trend engine's leak-watch inputs
PROCESS = Registry("koord_process")

ALL_REGISTRIES = (SCHEDULER, KOORDLET, MANAGER, DESCHEDULER, TRANSPORT,
                  PROCESS)


def expose_all(openmetrics: bool = False) -> str:
    """One scrape body over every component registry — the aggregate
    /metrics surface each binary's HTTP gateway serves (a koordlet
    process still exposes its transport metrics, a scheduler its
    koordlet-registry zeros, and so on: scrape configs stay uniform).

    The OpenMetrics body ends with the mandatory ``# EOF`` terminator —
    a scraper negotiating openmetrics via Accept would otherwise reject
    the whole exposition as truncated."""
    body = "".join(r.expose(openmetrics) for r in ALL_REGISTRIES)
    if openmetrics:
        body += "# EOF\n"
    return body


def quantile_from_buckets(bounds: Sequence[float],
                          cum_counts: Sequence[float],
                          total: float, q: float) -> float:
    """Prometheus ``histogram_quantile`` bucket interpolation over
    cumulative finite-bucket counts.

    ``cum_counts[i]`` is the number of observations <= ``bounds[i]``;
    ``total`` includes the +Inf bucket.  Observations landing past the
    last finite bound (the +Inf bucket) clamp to the highest finite
    bound — the quantile of data the buckets cannot resolve is the best
    bound they CAN name, exactly Prometheus's behavior.  Empty data
    returns the 0.0 sentinel."""
    if total <= 0 or not bounds:
        return 0.0
    q = min(max(q, 0.0), 1.0)
    rank = q * total
    for i, bound in enumerate(bounds):
        if cum_counts[i] >= rank:
            lower = bounds[i - 1] if i > 0 else 0.0
            below = cum_counts[i - 1] if i > 0 else 0.0
            in_bucket = cum_counts[i] - below
            if in_bucket <= 0:
                return bound
            return lower + (bound - lower) * (rank - below) / in_bucket
    return bounds[-1]   # rank falls in the +Inf bucket


def count_at_or_below(bounds: Sequence[float],
                      cum_counts: Sequence[float],
                      total: float, x: float) -> float:
    """Estimated observations <= ``x`` by linear interpolation within
    the containing bucket (the burn-rate engine's "good events" count
    for thresholds that are not exact bucket bounds).

    Observations in the +Inf bucket are NEVER counted at-or-below a
    finite ``x`` — the buckets cannot prove anything about them, and a
    threshold at/above the last finite bound must not silently bless a
    60s solve as meeting a 10s SLO (they count as bad, the conservative
    direction for an error budget)."""
    if total <= 0 or not bounds:
        return 0.0
    if x >= bounds[-1]:
        return float(cum_counts[-1])
    prev_bound, prev_cum = 0.0, 0.0
    for bound, cum in zip(bounds, cum_counts):
        if x < bound:
            width = bound - prev_bound
            if width <= 0:
                return float(cum)
            frac = max(0.0, (x - prev_bound)) / width
            return prev_cum + (cum - prev_cum) * frac
        prev_bound, prev_cum = bound, cum
    return float(cum_counts[-1])


def parse_openmetrics_flag(value) -> bool:
    """One parser for the ``openmetrics`` query/param flag across the
    debug surfaces: only explicit truthy spellings enable it (JSON
    ``false`` and the string "false" must NOT — an exemplar-suffixed
    body breaks classic Prometheus parsers)."""
    return str(value).strip().lower() in ("1", "true", "yes", "on")


def reset_all_for_tests() -> None:
    for registry in ALL_REGISTRIES:
        registry.reset_for_tests()

# Canonical instruments (names mirror the reference's).
scheduling_latency = SCHEDULER.histogram(
    "scheduling_duration_seconds",
    "Scheduling-cycle latency per phase (label: phase); aggregate by (le, "
    "phase)")
solver_batch_latency = SCHEDULER.histogram(
    "solver_batch_duration_seconds", "Batched filter/score/assign solve latency")
solver_device_latency = SCHEDULER.histogram(
    "solver_device_duration_seconds",
    "Device-side share of the batch solve: time spent blocking on the "
    "jitted solves' results (label: path=incremental|full_*) — wall "
    "minus this is host batch-build/dispatch/bookkeeping overhead")
round_flight_dumps = SCHEDULER.counter(
    "round_flight_dumps_total",
    "Round flight records dumped by the recorder (label: "
    "reason=slow|degraded)")
pending_pods = SCHEDULER.gauge("pending_pods", "Pods waiting to be scheduled")
incremental_dirty_fraction = SCHEDULER.gauge(
    "incremental_dirty_fraction",
    "Dirty fraction the incremental solve saw this round (label: "
    "kind=nodes|pods); drives the full-pass fallback flip")
incremental_solve_total = SCHEDULER.counter(
    "incremental_solve_rounds_total",
    "Batch solve rounds by path (label: path=incremental|full_cold|"
    "full_fallback|full_gang|full_dense|disabled) — full_fallback means "
    "the dirty fraction crossed the threshold, full_cold that no valid "
    "candidate cache existed, full_dense that a dense (hinted/topology) "
    "feasibility mask forced the full path")
incremental_dirty_pods = SCHEDULER.gauge(
    "incremental_dirty_pods",
    "Pods fully rescored by the last incremental round (new/changed pods "
    "plus pods whose cached candidates touched a dirty node)")
state_staleness_seconds = SCHEDULER.gauge(
    "state_staleness_seconds",
    "Age of the last applied sync event (delta or heartbeat) as of the "
    "last scheduling round; drives the degraded-mode flip")
degraded_mode = SCHEDULER.gauge(
    "degraded_mode",
    "1 while the scheduler is in stale-state degraded mode (BE admission "
    "suspended, full-pass solves), else 0")
degraded_transitions_total = SCHEDULER.counter(
    "degraded_transitions_total",
    "Degraded-mode flips (label: phase=enter|exit)")
degraded_suspended_pods = SCHEDULER.gauge(
    "degraded_suspended_pods",
    "Pods held out of the last round because degraded mode suspends "
    "BE/batch-dim admission")
solve_deadline_shed_total = SCHEDULER.counter(
    "solve_deadline_shed_total",
    "SOLVE_REQUESTs shed because their deadline expired before the solve "
    "could start (the caller already timed out; running it helps nobody)")
greedy_scan_rows = SCHEDULER.counter(
    "solver_greedy_scan_rows_total",
    "Rows handed to the exact greedy scan (the rescue pass over the batch "
    "engine's leftovers, the reservation pre-pass, a round below the batch "
    "threshold), by outcome: stepped (live at the scan's entry: one loop "
    "step each, per gang pass) or pruned (no node passed the entry filter, "
    "so none could at its own step: never visited).  Padded rows are not "
    "rows")
round_flight_overwritten = SCHEDULER.counter(
    "round_flight_overwritten_total",
    "Flight records evicted by ring overwrite (dump reasons are "
    "counted; silent eviction was not, ISSUE 5).  A full ring evicts "
    "one record per round: size the ring so this rate times your "
    "/debug/rounds polling interval stays well under the ring capacity, "
    "or evicted rounds were never observable")

# -- SLO burn-rate engine (slo_monitor.py) --
slo_burn_rate = SCHEDULER.gauge(
    "slo_burn_rate",
    "Error-budget burn rate per SLO and window (labels: slo, "
    "window=fast|slow); 1.0 = burning exactly the budget, >>1 = paging")
slo_breached = SCHEDULER.gauge(
    "slo_breached",
    "1 while the SLO's fast-burn alert is firing (label: slo); cleared "
    "with hysteresis once the fast window cools")
slo_alerts_total = SCHEDULER.counter(
    "slo_alerts_total",
    "SLO alert transitions (labels: slo, phase=fire|clear)")

# -- steady-state observatory (trend.py / selftelemetry.py, ISSUE 9) --
pods_enqueued_total = SCHEDULER.counter(
    "pods_enqueued_total",
    "Pods admitted into the scheduling queue (rsv:: reserve-pods "
    "included) — rate() of this is the arrival rate the churn load "
    "generator drives and the steady-state dashboards plot")
trend_verdict = SCHEDULER.gauge(
    "trend_verdict",
    "Long-horizon trend verdict per watched series (labels: series "
    "plus the series' own labels): -1 no_data, 0 steady, 1 drifting, "
    "2 leaking — set by each TrendEngine.evaluate and served at "
    "/debug/steady")
trend_slope_per_hour = SCHEDULER.gauge(
    "trend_slope_per_hour",
    "Fitted windowed slope per watched series, scaled to units/hour "
    "(labels: series plus the series' own labels)")

# -- multi-tenant round pipeline (scheduler/tenancy.py, ISSUE 11) --
tenant_count = SCHEDULER.gauge(
    "tenant_count",
    "Clusters multiplexed onto this scheduler's mesh by the tenancy "
    "front-end (0 = single-tenant scheduler, no front-end attached)")
tenant_admission_share = SCHEDULER.gauge(
    "tenant_admission_share",
    "Observed share of the last cycle's admitted pods per tenant "
    "(label: tenant) — under sustained overload this converges to the "
    "tenant's weight fraction (weighted deficit-round-robin admission)")
tenant_admitted = SCHEDULER.counter(
    "tenant_pods_admitted_total",
    "Pods admitted into solve rounds by the weighted-fair admission "
    "gate, per tenant (label: tenant); rate ratios between tenants are "
    "the fairness observable")
tenant_cycles = SCHEDULER.counter(
    "tenant_cycles_total",
    "Multi-tenant scheduling cycles by dispatch mode (label: "
    "mode=pipelined|batched|serial) — batched means one tenant-axis "
    "vmapped program solved every tenant, pipelined that per-tenant "
    "device solves overlapped host commits, serial the fallback")
tenant_cycle_latency = SCHEDULER.histogram(
    "tenant_cycle_duration_seconds",
    "Wall time of one multi-tenant scheduling cycle (every tenant's "
    "round, device and host halves)")
pipeline_host_wait_fraction = SCHEDULER.gauge(
    "pipeline_host_wait_fraction",
    "Share of the last cycle's wall the host spent BLOCKED on device "
    "solve results (sum of block waits / cycle wall).  Serial "
    "single-tenant-at-a-time operation pins this near the device's "
    "share of the round; the pipelined overlap drives it toward zero "
    "because solves execute while other tenants' commits run")

# -- critical-path observatory (timeline.py, ISSUE 18) --
host_wait_attribution = SCHEDULER.gauge(
    "host_wait_attribution",
    "Decomposition of the last cycle's WHOLE wall into fractions that "
    "sum to 1.0 (label: cause — timeline.ATTRIBUTION_CAUSES).  The "
    "device_block bucket equals pipeline_host_wait_fraction by "
    "construction (same block_until_ready intervals); the remaining "
    "causes (dispatch, deltasync_apply, build_batch, bind_commit, "
    "json_codec, lock_wait, host_other) decompose its complement, and "
    "unattributed is the explicit residual the phase-accounting "
    "invariant test pins under 5%")
device_idle_fraction = SCHEDULER.gauge(
    "device_idle_fraction",
    "Share of the last cycle's wall with NO solve in flight on the "
    "device, derived from the dispatch/block edges of every tenant's "
    "round — the headroom the pipelined overlap has not yet claimed")
critical_path_seconds = SCHEDULER.gauge(
    "critical_path_seconds",
    "Seconds of the last cycle's critical-path covering chain per "
    "cause (label: cause); topk(1, ...) names the dominant cause the "
    "ROADMAP item-5 perf attack should aim at.  Every cause is "
    "republished each cycle so cleared ones read 0")
timeline_segments_dropped = SCHEDULER.counter(
    "timeline_segments_dropped_total",
    "Timeline records (spans, and wait observations: ISSUE 34) the "
    "recorder's full rings pushed out before any window read them; "
    "back-to-back spans of one name are one record, so a steady "
    "scheduler sits at zero")
explanation_queue_purged = SCHEDULER.counter(
    "explanation_queue_purged_total",
    "QUEUED ScheduleExplanation entries that ExplanationStore.delete / "
    "delete_many removed because their pod bound before a drain wrote "
    "them: the only case in which a delete walks the queue.  Zero means "
    "no pod that bound had a failure waiting in the queue")

# -- pod-journey ledger (journey.py, ISSUE 20) --
pod_journey_latency_seconds = SCHEDULER.gauge(
    "pod_journey_latency_seconds",
    "Per-pod scheduling-journey latency quantiles from the always-on "
    "journey ledger's mergeable log-bucketed sketches (labels: tenant, "
    "qos, stage=e2e|ingest|queue_wait|solve|commit, q=0.5|0.99).  "
    "Unlike the round-scoped scheduling_duration histogram these are "
    "TRUE per-pod arrival->bind quantiles with <=1% relative error, "
    "published by the SloMonitor pre-sample hook each sweep")

# -- process self-telemetry (selftelemetry.py) --
process_rss_bytes = PROCESS.gauge(
    "rss_bytes", "Resident set size (proc statm; label: binary)")
process_open_fds = PROCESS.gauge(
    "open_fds", "Open file descriptors (label: binary)")
process_threads = PROCESS.gauge(
    "threads", "Live Python threads (label: binary)")
process_alloc_blocks = PROCESS.gauge(
    "alloc_blocks",
    "Interpreter-allocated memory blocks (sys.getallocatedblocks; "
    "label: binary) — a cheap, monotone-under-leak heap signal")
process_gc_objects = PROCESS.gauge(
    "gc_objects",
    "Generation-0 gc-tracked objects (label: binary)")
process_gc_collections = PROCESS.gauge(
    "gc_collections",
    "Cumulative gc collections across generations (label: binary)")

# -- DeviceShare inside the batched solve (ops/deviceshare.py) --
deviceshare_grants = SCHEDULER.counter(
    "deviceshare_grants_total",
    "Device-requesting proposals that reached the device stage, by "
    "outcome: granted (bound with its devices), lost_race (accepted on "
    "the node's aggregate rows in a round in which a pod ahead of it took "
    "the device; it proposed again), no_device (left the round unbound)")
deviceshare_inventory_events = SCHEDULER.counter(
    "deviceshare_inventory_events_total",
    "Device inventories applied (node_upsert with devices, node_devices): "
    "one row of the device table rewritten each")
deviceshare_whole_free_devices = SCHEDULER.gauge(
    "deviceshare_whole_free_devices",
    "Usable GPUs with nothing granted on them, after the last commit")

# -- deferred request accounting (scheduler/snapshot.py) --
snapshot_requested_folds = SCHEDULER.counter(
    "snapshot_requested_folds_total",
    "Folds of the host-pending Reserve/Unreserve delta into "
    "node_requested: one device op each, at the first read of the state "
    "after a reserve or a release; a read with nothing pending folds "
    "nothing and is not counted")
snapshot_requested_deltas_folded = SCHEDULER.counter(
    "snapshot_requested_deltas_folded_total",
    "Reserve/Unreserve calls whose vectors those folds carried to the "
    "device; deltas per fold is how many per-pod device ops one fold "
    "stood for")

# -- JAX solver introspection (ops/introspection.py) --
solver_recompiles = SCHEDULER.counter(
    "solver_recompiles_total",
    "Jit-cache misses (trace+compile) of the solver's jitted entry "
    "points per shape bucket (labels: fn, shape) — a steady-state "
    "scheduler should sit at zero rate; increments mean shape churn")
solver_load_seconds = SCHEDULER.counter(
    "solver_load_seconds_total",
    "Wall seconds of the calls that grew a solver entry point's jit "
    "cache (label: fn): trace, lower, compile or persistent-cache "
    "load, and dispatch — what a process pays before its first warm "
    "round, beside solver_recompiles_total's count of the same calls")
solver_jit_cache_size = SCHEDULER.gauge(
    "solver_jit_cache_size",
    "Live jit-cache entries per instrumented solver entry point "
    "(label: fn); bounded by the power-of-two shape bucketing")
solver_device_bytes = SCHEDULER.gauge(
    "solver_device_bytes",
    "Device-resident bytes of the solver's persistent tensors (label: "
    "kind=cluster_state|candidate_cache; per-device rows additionally "
    "carry shard=<device id> when the solve mesh is active)")
solver_shard_count = SCHEDULER.gauge(
    "solver_shard_count",
    "Nodes-axis size of the active solver mesh (1 = single-device "
    "solve; parallel/sharded.py shard_map path engaged when > 1)")
solver_axis_shard_count = SCHEDULER.gauge(
    "solver_axis_shard_count",
    "Per-axis size of the active 2-D solver mesh (label: "
    "axis=pods|nodes; both 1 for a single-device solve) — the split "
    "solver_shard_count can't express once the pods axis is > 1")
solver_batch_padding_waste = SCHEDULER.gauge(
    "solver_batch_padding_waste",
    "Padding-waste fraction of the last PodBatch: (capacity - live "
    "pods) / capacity — the device memory and FLOPs spent on rows the "
    "power-of-two bucketing padded in")

# -- placement explainability (ops/explain.py, ISSUE 6) --
unschedulable_pods = SCHEDULER.gauge(
    "unschedulable_pods",
    "Pods the last round left unplaced (or suspended/gang-parked), by "
    "attributed top reject reason (label: reason — ops/explain."
    "REASON_NAMES: per-dim fit, usage_threshold, affinity, plus the "
    "pod-level gates quota/gang_barrier/degraded_suspended); every "
    "reason label is republished each round so cleared reasons read 0")
filter_reject_fraction = SCHEDULER.histogram(
    "filter_reject_fraction",
    "Fraction of cluster nodes each filter stage rejected, averaged "
    "over a round's unplaced pods (label: reason) — which constraint "
    "is actually binding when pods go unschedulable",
    buckets=(0.01, 0.05, 0.1, 0.25, 0.5, 0.75, 0.9, 0.99, 1.0))
capacity_slack = SCHEDULER.gauge(
    "capacity_slack_fraction",
    "Request-free capacity fraction per resource dimension over valid "
    "nodes: sum(allocatable - requested) / sum(allocatable) (label: "
    "dim) — the per-dim headroom left before fit_<dim> rejections "
    "dominate")

# -- solve-quality mode (quality/lp_pack + quality/topo_gang, ISSUE 13) --
solver_quality_mode = SCHEDULER.gauge(
    "solver_quality_mode",
    "Configured solve-quality mode: 0=off (greedy only), 1=lp (every "
    "eligible round solves with the LP-relaxation packing engine), "
    "2=auto (escalate only rounds whose result leaves "
    "capacity_slack_fraction above the threshold)")
quality_rounds = SCHEDULER.counter(
    "quality_rounds_total",
    "Rounds solved on the LP-relaxation quality path (labels: "
    "mode=lp|auto, outcome=complete|partial — partial means the round "
    "still diagnosed failures after the quality solve and the exact "
    "rescue pass)")
quality_iterations = SCHEDULER.histogram(
    "quality_iterations",
    "Rounding phases the LP quality solve executed per round (bounded "
    "by the engine's rounding_iters — a round pinned at the bound "
    "means contention never cleared and the final prefix resolution "
    "did the placing)",
    buckets=(1.0, 2.0, 3.0, 4.0, 6.0, 8.0, 12.0, 16.0))
quality_slack_recovered = SCHEDULER.gauge(
    "quality_slack_recovered_fraction",
    "Fraction of total allocatable capacity the last quality round "
    "turned from free slack into placements, per resource dimension "
    "(label: dim): (free_before - free_after) / allocatable")

# -- forecast plane (forecast/, ISSUE 15) --
forecast_horizon_seconds = SCHEDULER.gauge(
    "forecast_horizon_seconds",
    "The forecast plane's current prediction horizon: the base horizon "
    "stretched by the diurnal trend slope (plane.horizon_for) — a "
    "ramping cluster looks further ahead")
forecast_error_fraction = SCHEDULER.gauge(
    "forecast_error_fraction",
    "Forecast error of the previous prediction window, per resource "
    "dimension (label: dim): sum|predicted - realized peak| / "
    "sum(realized peak) over nodes that saw usage")
forecast_admission_reserved_fraction = SCHEDULER.gauge(
    "forecast_admission_reserved_fraction",
    "Fraction of cluster allocatable the predictive-admission reserve "
    "charged into the last forecast round's filter/score accounting "
    "(forecast growth not yet visible in observed usage)")
forecast_evictions_prestaged = SCHEDULER.counter(
    "forecast_evictions_prestaged_total",
    "Reservation-first migrations pre-staged off nodes FORECAST to "
    "cross the LowNodeLoad high threshold (proactive rebalance) — "
    "each one is a reactive emergency eviction that never had to "
    "happen")

# -- failure drills (drills/, ISSUE 17) --
drill_active = SCHEDULER.gauge(
    "drill_active",
    "1 while a failure drill scenario is running against this control "
    "plane (label: scenario) — correlates every other panel's wobble "
    "with the drill that injected it; zero in production")
drill_recovery_duration_seconds = SCHEDULER.histogram(
    "drill_recovery_duration_seconds",
    "Measured RTO per drill: inject (kill/storm/restart) to the verdict "
    "engine's reconvergence fixpoint (all live pods bound, degraded "
    "mode exited, watch views caught up to the service rv)",
    buckets=(0.1, 0.25, 0.5, 1.0, 2.0, 5.0, 10.0, 30.0, 60.0))
leader_failovers_total = SCHEDULER.counter(
    "leader_failovers_total",
    "Observed scheduler leadership hand-offs (a different identity "
    "holds the lease than the previous observation) — drills assert "
    "exactly the scripted number happened")
checkpoint_restore_duration_seconds = SCHEDULER.histogram(
    "checkpoint_restore_duration_seconds",
    "Warm-restart checkpoint restore time (drills/checkpoint.restore): "
    "load + apply of the host snapshot and replay cursor, EXCLUDING "
    "the deltasync catch-up that follows",
    buckets=(0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0))

be_suppress_cpu_cores = KOORDLET.gauge(
    "be_suppress_cpu_cores", "CPU cores currently allowed for BE")
pod_eviction_total = KOORDLET.counter(
    "pod_eviction_total", "Node-side evictions by reason")
cpu_burst_total = KOORDLET.counter(
    "cpu_burst_total", "CPU burst quota adjustments")
container_cpi = KOORDLET.gauge("container_cpi", "Cycles per instruction")
psi_cpu_some_avg10 = KOORDLET.gauge("psi_cpu_some_avg10", "CPU PSI some avg10")

batch_resource_allocatable = MANAGER.gauge(
    "batch_resource_allocatable", "Batch allocatable per node/resource")
node_metric_expired = MANAGER.gauge(
    "node_metric_expired", "1 when a node's metric report is stale")
colocation_patches_total = MANAGER.counter(
    "colocation_patches_total",
    "node_allocatable patches pushed by the colocation loop")
colocation_push_frames_total = MANAGER.counter(
    "colocation_push_frames_total",
    "run-form STATE_PUSH frames the colocation loop sent (patches / "
    "frames is the run length; at most wire.STATE_PUSH_RUN_MAX)")
colocation_push_failures_total = MANAGER.counter(
    "colocation_push_failures_total",
    "colocation-loop pushes lost to a wedged sidecar (retried next tick)")
colocation_connect_failures_total = MANAGER.counter(
    "colocation_connect_failures_total",
    "colocation-loop sidecar reconnect attempts that failed")
colocation_sync_reason_total = MANAGER.counter(
    "colocation_sync_reason_total",
    "noderesource patches by why the node was synced (label: "
    "reason=first|time_gap|diff|degraded)")
colocation_watch_events_total = MANAGER.counter(
    "colocation_watch_events_total",
    "node deltas the manager's watch applied to its view")

rpc_deadline_shed_total = TRANSPORT.counter(
    "rpc_deadline_shed_total",
    "Requests shed at the channel layer because deadline_ms had already "
    "expired at dispatch (label: type=frame type)")
breaker_state = TRANSPORT.gauge(
    "circuit_breaker_state",
    "Dial circuit breaker state per target: 0=closed, 1=half-open, 2=open")
breaker_transitions_total = TRANSPORT.counter(
    "circuit_breaker_transitions_total",
    "Breaker state transitions (labels: target, to)")
dial_attempts_total = TRANSPORT.counter(
    "dial_attempts_total",
    "Reconnecting-client dial attempts (label: outcome=ok|refused|"
    "bootstrap_failed|open — refused means the dial itself failed, "
    "bootstrap_failed that the peer accepted but the on_connect "
    "bootstrap did not, open that the circuit refused to dial at all)")
faults_injected_total = TRANSPORT.counter(
    "faults_injected_total",
    "Injected transport faults by kind (chaos harness only; zero in "
    "production)")
sync_gap_resyncs_total = TRANSPORT.counter(
    "sync_gap_resyncs_total",
    "Watch-stream rv gaps detected by a sync client (a lost/reordered "
    "delta): the client tears its connection down and re-HELLOs")
sync_binding_backlog = TRANSPORT.gauge(
    "sync_binding_backlog",
    "Committed deltasync events queued for local-binding apply right "
    "now (StateSyncService._binding_queue depth) — bindings drain it "
    "behind the scheduler lock, so sustained growth means solve rounds "
    "can no longer keep up with the arrival process")
sync_binding_backlog_peak = TRANSPORT.gauge(
    "sync_binding_backlog_peak",
    "High-water mark of the local-binding backlog since process start "
    "(the watermark the steady-state soak bounds and the trend engine "
    "watches); a run-form STATE_PUSH frame commits up to 1,024 events "
    "before its one drain, so a tick's frame length is its floor")
sync_delta_frames_total = TRANSPORT.counter(
    "sync_delta_frames_total",
    "Committed deltasync EVENTS by whether anyone was there to be sent "
    "them (label: outcome=built|no_recipient — built means a live "
    "watcher was connected at the commit and was told there is news; "
    "no_recipient that nobody was, so nothing was queued or built: a "
    "later HELLO serves the event from the log or the snapshot).  "
    "Counted only where a server is attached.  The frames themselves "
    "are sync_delta_frames_sent_total")
sync_delta_frames_sent_total = TRANSPORT.counter(
    "sync_delta_frames_sent_total",
    "Live DELTA frames handed to a connection's socket, summed over "
    "connections: the committer's ready single-event frame for a "
    "watcher that is caught up and idle, or the ONE frame a "
    "connection's sender thread builds from the delta log for the "
    "whole run of events the connection lacked when it got there")
sync_delta_events_sent_total = TRANSPORT.counter(
    "sync_delta_events_sent_total",
    "Events carried by the frames of sync_delta_frames_sent_total, "
    "summed over connections; events / frames is the run length (1 "
    "where every watcher keeps up with every commit)")
sync_watch_cursor_lag_events = TRANSPORT.gauge(
    "sync_watch_cursor_lag_events",
    "How far behind a watcher was, in events, when its connection's "
    "sender thread last took a run from the delta log (label: "
    "quantity=last|peak — last is the newest run's length over all "
    "connections, peak the longest since process start).  Set once per "
    "run, never per event.  The line to alert on is the log's retention "
    "(4,096 events): a watcher whose cursor falls out of it is poisoned "
    "and comes back by snapshot; a pusher waiting for its reply and a "
    "watcher that keeps up are sent ready single-event frames and never "
    "set it")
sync_resyncs_total = TRANSPORT.counter(
    "sync_resyncs_total",
    "Server-requested resyncs honored by a reconnecting client (ERROR "
    "frame with resync: true — e.g. a push for a node the restarted "
    "service no longer knows)")
wire_codec_seconds = TRANSPORT.histogram(
    "wire_codec_duration_seconds",
    "JSON+array payload codec wall time per operation (label: "
    "op=encode|decode) — the json_codec slice of the host-wait "
    "attribution (ISSUE 18); rising encode p99 at flat payload bytes "
    "means the control doc grew, not the tensors",
    buckets=(0.00005, 0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005,
             0.01, 0.025, 0.05, 0.1, 0.25, 1.0))
wire_payload_bytes = TRANSPORT.histogram(
    "wire_payload_bytes",
    "Encoded frame payload size in bytes per operation (label: "
    "op=encode|decode): json section + raw array section together",
    buckets=(256, 1024, 4096, 16384, 65536, 262144, 1048576, 4194304,
             16777216, 67108864))

descheduler_evictions_total = DESCHEDULER.counter(
    "pod_evictions_total", "Descheduler evictions by profile/reason")
migration_jobs = DESCHEDULER.gauge(
    "migration_jobs", "PodMigrationJobs by phase")
descheduler_victims_total = DESCHEDULER.counter(
    "victims_total", "Pods a balance plugin chose to move (label: plugin)")
migration_reserve_rounds = DESCHEDULER.counter(
    "migration_reserve_rounds_total",
    "Scheduling rounds run to place migration reservations: one per "
    "reconcile that let any job run, however many jobs")
migration_jobs_arbitrated = DESCHEDULER.counter(
    "migration_jobs_arbitrated_total",
    "Pending PodMigrationJobs by what arbitration did with them (label: "
    "outcome=allowed|node|namespace|workload: let run, or held back by "
    "that group's limit)")
