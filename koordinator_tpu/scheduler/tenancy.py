"""Multi-tenant round pipeline: N clusters on one mesh (ISSUE 11).

"Millions of users" is many clusters, not one.  This module multiplexes
several clusters — *tenants* — onto one scheduler process and one solve
mesh.  Each tenant keeps its OWN control-plane state: a
:class:`~koordinator_tpu.scheduler.snapshot.ClusterSnapshot`, candidate
cache, quota tree, staleness watchdog and degraded mode — isolation is
structural, one tenant's stale sync feed cannot suspend another's
admission — while all tenants share ONE
:class:`~koordinator_tpu.scheduler.solver_kit.SolverKit` (one jit cache,
one mesh, one recompile ledger).

Three dispatch modes, best first:

- **batched** — when every tenant's round is shape-aligned (same node
  capacity, same pod bucket, gangless, selector-mask path, compatible
  quota shapes, single-device), the cycle solves as ONE tensor program
  with a leading tenant axis: per-tenant states/batches stack to
  (T, N, R)/(T, P, ...) pytrees and a ``jax.vmap`` of candidate
  selection + the first propose/accept pass runs in one dispatch.
  Per-tenant slices are bit-identical to the serial solves (integer
  ranking keys; a finished tenant's extra ``while_loop`` iterations are
  no-ops), proven in tests/test_tenancy.py.
- **pipelined** — otherwise, per-tenant rounds ride the host/device
  split (``Scheduler.round_device``/``round_host``): tenant B's device
  solve is DISPATCHED before tenant A's host commit runs, so the mesh
  executes B's solve while the host binds A's pods, serves A's debug
  traffic, and applies deltas — round N+1's solve overlaps round N's
  commit, which is what deletes the host-commit device idle gap.
- **serial** — ``pipeline=False`` fallback: plain ``schedule_round``
  per tenant (the before-baseline bench_stages measures against).

Admission is **weighted deficit-round-robin**: each cycle distributes
``cycle_pod_budget`` credits in proportion to tenant weights (unused
share redistributes to backlogged tenants), every tenant's round admits
at most its credit, and admitted pods are charged back — under
sustained overload admitted shares converge to weight fractions
(Priority Matters' per-tenant fairness inside one batched solve, not
per-cluster silos).

The double-buffered hand-off and its donation argument are documented
on ``Scheduler._round_device`` and in docs/multitenancy.md; koordlint's
donation-safety corpus seeds both the blessed swap and the
stash-the-in-flight-buffer anti-idiom.
"""

from __future__ import annotations

import collections
import dataclasses
import threading
import time
from functools import partial

import numpy as np

from koordinator_tpu import metrics, timeline

# JAX is imported lazily inside methods where possible, but the batched
# path is core to this module; the scheduler stack already pulls JAX in.
import jax
import jax.numpy as jnp


@dataclasses.dataclass
class TenantSpec:
    """One cluster's identity and share of the mesh."""

    name: str
    #: weighted-fair admission share (relative; DRR credits accrue
    #: proportionally to this each cycle)
    weight: float = 1.0
    #: the tenant's ClusterSnapshot row capacity at creation (grows by
    #: power-of-two buckets like any snapshot)
    node_capacity: int = 64


class Tenant:
    """A tenant's scheduler plus its fair-admission ledger."""

    def __init__(self, spec: TenantSpec, scheduler):
        self.spec = spec
        self.scheduler = scheduler
        #: DRR deficit credit, in pods; topped up each cycle by
        #: weight share, drawn down by admitted pods
        self.credit = 0.0
        self.admitted_total = 0
        self.last_admitted = 0
        self.rounds = 0

    @property
    def name(self) -> str:
        return self.spec.name


class TenantScheduler:
    """Front-end multiplexing N tenants onto one shared solver kit.

    Duck-type compatible with the single-tenant ``Scheduler`` where the
    transport layer needs it (``lock`` + ``schedule_round`` for
    SolveService; ``stop`` for the binary assembly), so one listen
    socket can drive multi-tenant cycles.
    """

    def __init__(self, cycle_pod_budget: int = 4096,
                 pipeline: bool = True,
                 batch_tenant_axis: bool = True,
                 scheduler_defaults: dict | None = None,
                 solver_kit=None):
        from koordinator_tpu.scheduler.solver_kit import SolverKit

        #: pods admitted per cycle across ALL tenants (the DRR quantum)
        self.cycle_pod_budget = cycle_pod_budget
        self.pipeline = pipeline
        self.batch_tenant_axis = batch_tenant_axis
        #: ctor kwargs applied to every tenant's Scheduler (e.g.
        #: batch_solver_threshold, incremental_solve) unless overridden
        #: per add_tenant call
        self.scheduler_defaults = dict(scheduler_defaults or {})
        #: a passed kit is SHARED (e.g. bench_stages times serial vs
        #: pipelined fronts on one jit cache); otherwise build our own
        self.kit = solver_kit if solver_kit is not None else SolverKit()
        #: front-end lock: serializes cycles (SolveService acquires it
        #: the way it acquires a Scheduler's round lock)
        self.lock = threading.RLock()
        self._tenants: dict[str, Tenant] = {}
        self.cycle_seq = 0
        self.last_mode = "none"
        self.last_cycle_s = 0.0
        self.last_host_wait_fraction = 0.0
        #: the last cycle's reconstructed timeline doc (ISSUE 18) —
        #: None until a cycle ran with the recorder armed
        self.last_timeline = None
        #: jit cache for the tenant-axis batched programs, keyed by the
        #: static solve knobs (shapes retrace inside jax.jit as usual)
        self._batched_fns: dict[tuple, object] = {}
        #: jit cache for the QUALITY tenant-axis program (ISSUE 19):
        #: vmap of lp_pack_assign, keyed by has_quota
        self._quality_fns: dict[bool, object] = {}
        #: ONE shared ScoringConfig handed to tenants that don't bring
        #: their own: the batched program broadcasts a single config
        #: over the tenant axis, and _batched_eligible requires config
        #: IDENTITY — per-tenant default instances would silently
        #: disqualify every cycle
        self._default_config = None
        #: demand snapshot of the current cycle (tenant -> pending
        #: count), taken once by _admission_limits under each tenant's
        #: lock and reused by _batch_floor so the floor and the limits
        #: describe the SAME queue state
        self._cycle_demand: dict[str, int] = {}
        #: SLO monitor / trend engine attached by the binary assembly —
        #: same attachment points a single-tenant Scheduler exposes
        self.slo_monitor = None
        self.trend_engine = None
        #: ha.LeaderElector — leadership gates the WHOLE cycle here (a
        #: standby front must not decide for ANY tenant); per-tenant
        #: schedulers run ungated under the front
        self.elector = None
        #: per-tenant StateSyncServices (binary assembly) and teardown
        #: hooks for the extra per-tenant listen sockets
        self.tenant_syncs: dict = {}
        self.closers: list = []

    # -- tenant lifecycle ----------------------------------------------------

    def add_tenant(self, spec: TenantSpec, **scheduler_kwargs) -> Tenant:
        """Create a tenant: its own snapshot/quota/degraded state, the
        SHARED solver kit."""
        from koordinator_tpu.scheduler.scheduler import Scheduler
        from koordinator_tpu.scheduler.snapshot import ClusterSnapshot

        with self.lock:
            if spec.name in self._tenants:
                raise ValueError(f"tenant {spec.name!r} already exists")
            kwargs = {**self.scheduler_defaults, **scheduler_kwargs}
            if kwargs.get("config") is None:
                if self._default_config is None:
                    from koordinator_tpu.ops.assignment import ScoringConfig

                    self._default_config = ScoringConfig.default()
                kwargs["config"] = self._default_config
            snapshot = kwargs.pop("snapshot", None) or ClusterSnapshot(
                capacity=spec.node_capacity)
            sched = Scheduler(snapshot, tenant=spec.name,
                              solver_kit=self.kit, **kwargs)
            sched.tenant_front = self
            tenant = Tenant(spec, sched)
            self._tenants[spec.name] = tenant
            metrics.tenant_count.set(float(len(self._tenants)))
            return tenant

    def tenant(self, name: str) -> Tenant:
        return self._tenants[name]

    def tenants(self) -> list[Tenant]:
        return list(self._tenants.values())

    @property
    def primary(self):
        """The first tenant's scheduler — the attachment point for
        surfaces that expect one Scheduler (flight dumps on SLO
        breach, per-tenant DebugService instances serve their own)."""
        first = next(iter(self._tenants.values()), None)
        return first.scheduler if first is not None else None

    def stop(self) -> None:
        if self.slo_monitor is not None:
            self.slo_monitor.stop()
        for tenant in self._tenants.values():
            tenant.scheduler.stop()
        for closer in reversed(self.closers):
            try:
                closer()
            except Exception:  # noqa: BLE001 — best-effort teardown
                pass
        self.closers.clear()

    # -- weighted-fair admission (deficit round robin) -----------------------

    def _admission_limits(self) -> dict[str, int]:
        """Top up each tenant's DRR credit by its weight share of the
        cycle budget, redistribute share no backlog can use, and return
        per-tenant admission limits for this cycle's rounds."""
        tenants = list(self._tenants.values())
        if not tenants:
            return {}
        demand: dict[str, int] = {}
        for t in tenants:
            with t.scheduler.lock:
                demand[t.name] = len(t.scheduler.pending)
        # one demand snapshot per cycle: _batch_floor reuses it so the
        # common pod bucket describes the same queue state as the limits
        self._cycle_demand = dict(demand)
        wsum = sum(max(t.spec.weight, 0.0) for t in tenants) or 1.0
        budget = float(self.cycle_pod_budget)
        # waterfill: hand out weight-proportional share, move share no
        # backlog can consume to still-hungry tenants (bounded passes —
        # each pass either satisfies someone or terminates)
        share = {t.name: budget * max(t.spec.weight, 0.0) / wsum
                 for t in tenants}
        for _ in range(len(tenants)):
            surplus = 0.0
            hungry: list[Tenant] = []
            for t in tenants:
                # credit is invariantly >= 0 (admission never exceeds
                # int(credit)), so a tenant's useful share is its backlog
                room = float(demand[t.name])
                if share[t.name] > room:
                    surplus += share[t.name] - room
                    share[t.name] = room
                elif demand[t.name] > share[t.name]:
                    hungry.append(t)
            if surplus <= 0.0 or not hungry:
                break
            hsum = sum(max(t.spec.weight, 0.0) for t in hungry) or 1.0
            for t in hungry:
                share[t.name] += surplus * max(t.spec.weight, 0.0) / hsum
        limits: dict[str, int] = {}
        for t in tenants:
            # credit carries fractional share across cycles (classic
            # DRR) but is clamped to one budget so an idle tenant
            # cannot bank unbounded burst rights
            t.credit = min(t.credit + share[t.name], budget)
            limits[t.name] = max(int(t.credit), 0)
        return limits

    # -- the cycle -----------------------------------------------------------

    def schedule_round(self):
        """SolveService-compatible entry: run one full cycle and merge
        the per-tenant results under ``tenant/pod`` keys."""
        from koordinator_tpu.scheduler.scheduler import SchedulingResult

        results = self.schedule_cycle()
        merged = SchedulingResult({}, {}, 0)
        for name, result in results.items():
            merged.round_pods += result.round_pods
            for pod, node in result.assignments.items():
                merged.assignments[f"{name}/{pod}"] = node
            for pod, diag in result.failures.items():
                merged.failures[f"{name}/{pod}"] = diag
            for pod, nom in result.nominations.items():
                merged.nominations[f"{name}/{pod}"] = nom
        return merged

    def schedule_cycle(self) -> dict:
        """One multi-tenant scheduling cycle: weighted-fair admission,
        then every tenant's round — batched on the tenant axis when
        shape-aligned, pipelined otherwise (round N+1's device solve
        overlaps round N's host commit), serial as the opt-out."""
        with self.lock:
            if self.elector is not None and not self.elector.tick():
                # standby front: keep syncing every tenant's state,
                # decide nothing for anyone
                return {}
            self.cycle_seq += 1
            t0 = time.perf_counter()
            with timeline.RECORDER.section("host_other",
                                           "tenancy.admission"):
                limits = self._admission_limits()
            order = [t for t in self._tenants.values()]
            results: dict = {}
            if not order:
                return results
            # pipeline=False is the full opt-out: plain serial rounds,
            # whatever batch_tenant_axis says (the batched path's
            # misalignment fallback is itself pipelined)
            if not self.pipeline:
                mode = self._cycle_serial(order, limits, results)
            elif self.batch_tenant_axis:
                mode = self._cycle_batched(order, limits, results)
            else:
                mode = self._cycle_pipelined(order, limits, results)
            wall = time.perf_counter() - t0
            device_wait = sum(t.scheduler._solve_device_s for t in order)
            self.last_mode = mode
            self.last_cycle_s = wall
            self.last_host_wait_fraction = (
                min(device_wait / wall, 1.0) if wall > 0 else 0.0)
            metrics.tenant_cycles.inc(labels={"mode": mode})
            metrics.tenant_cycle_latency.observe(wall)
            metrics.pipeline_host_wait_fraction.set(
                self.last_host_wait_fraction)
            admitted_cycle = sum(t.last_admitted for t in order) or 1
            for t in order:
                metrics.tenant_admission_share.set(
                    t.last_admitted / admitted_cycle,
                    labels={"tenant": t.name})
            if timeline.RECORDER.enabled:
                # timeline observatory (ISSUE 18): reconstruct the
                # cycle's gantt, attribute its wall, publish the
                # host_wait_attribution family, and back-annotate every
                # tenant's flight records with the critical-path verdict
                doc = timeline.RECORDER.finish_cycle(
                    self.cycle_seq, t0, t0 + wall, mode=mode)
                if doc is not None:
                    self.last_timeline = doc
                    for t in order:
                        t.scheduler.flight_recorder.annotate_round(
                            t.scheduler.round_seq, t.name,
                            cycle_seq=doc["cycle"],
                            cycle_critical_cause=doc["critical_cause"],
                            cycle_critical_seconds=doc[
                                "critical_seconds"])
            return results

    def _begin_round(self, tenant: Tenant, limits: dict[str, int]):
        """Acquire the tenant's round lock and apply its admission cap.
        Caller owns releasing via :meth:`_end_round`."""
        sched = tenant.scheduler
        tl_armed = timeline.RECORDER.enabled
        t0 = time.perf_counter() if tl_armed else 0.0
        sched.lock.acquire()
        if tl_armed:
            # contention with the sync reader threads (deltasync
            # applies hold the same lock): the lock_wait slice of the
            # host-wait attribution
            timeline.RECORDER.add(t0, time.perf_counter(), "lock_wait",
                                  "round_lock.acquire", tenant.name)
        sched.round_pod_limit = limits.get(tenant.name)

    def _end_round(self, tenant: Tenant) -> None:
        sched = tenant.scheduler
        sched.round_pod_limit = None
        sched.lock.release()

    def _account_round(self, tenant: Tenant, handle) -> None:
        admitted = len(handle.pods)
        tenant.last_admitted = admitted
        tenant.admitted_total += admitted
        tenant.rounds += 1
        tenant.credit -= admitted
        if admitted:
            metrics.tenant_admitted.inc(admitted,
                                        labels={"tenant": tenant.name})

    def _cycle_serial(self, order, limits, results) -> str:
        for t in order:
            self._begin_round(t, limits)
            try:
                with t.scheduler.lock:
                    handle = t.scheduler.round_device()
                    self._account_round(t, handle)
                    results[t.name] = t.scheduler.round_host(handle)
            finally:
                self._end_round(t)
        return "serial"

    def _cycle_pipelined(self, order, limits, results) -> str:
        """Depth-1 software pipeline over tenants: dispatch tenant i+1's
        device solve BEFORE committing tenant i, so the device executes
        one tenant's solve while the host binds another's pods.  Locks
        are acquired in cycle order and each is held exactly across its
        tenant's two halves (RLock self-edges are exempt from the
        lock-discipline order graph; distinct tenants' locks are only
        ever taken in the fixed cycle order)."""
        pending: collections.deque = collections.deque()

        def commit(entry) -> None:
            t, handle = entry
            try:
                results[t.name] = t.scheduler.round_host(handle)
            finally:
                self._end_round(t)

        try:
            for t in order:
                self._begin_round(t, limits)
                try:
                    handle = t.scheduler.round_device()
                    self._account_round(t, handle)
                except Exception:
                    self._end_round(t)
                    raise
                pending.append((t, handle))
                # depth 1: the previous tenant commits while this
                # tenant's solve executes on device
                while len(pending) > 1:
                    commit(pending.popleft())
            while pending:                  # the cycle's last commit
                commit(pending.popleft())
        finally:
            # exception drain — every dispatched round still COMMITS
            # (its solve already charged the device-side accounting;
            # dropping it would strand phantom placements).  A commit
            # failing while we are already unwinding must not leak the
            # remaining tenants' locks, so failures here are swallowed
            # (commit's own finally released that tenant's lock).
            while pending:
                try:
                    commit(pending.popleft())
                except Exception:  # noqa: BLE001 — already unwinding
                    pass
        return "pipelined"

    # -- tenant-axis batched dispatch ---------------------------------------

    @staticmethod
    def _stack(trees):
        """Stack a list of congruent pytrees on a new leading tenant
        axis (None leaves stay None)."""
        return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)

    @staticmethod
    def _unstack(tree, i: int):
        return jax.tree.map(lambda x: x[i], tree)

    def _batched_fn(self, key: tuple):
        """The jitted tenant-axis program for one (method, rounds,
        has_quota) signature: vmap of candidate selection + the
        first propose/accept pass.  The stacked state is donated (it is
        a stacking COPY — the per-tenant originals stay live until each
        scheduler's blessed swap in round_adopt_batched)."""
        fn = self._batched_fns.get(key)
        if fn is not None:
            return fn
        method, rounds, has_quota = key
        from koordinator_tpu.ops import batch_assign as ba

        def one_tenant(state, batch, quota, cfg):
            ck, cn, cs = ba.select_candidates(
                state, batch, cfg, k=min(ba.CAND_K, state.capacity),
                method=method, with_scores=True)
            a, st, q, est = ba.assign_round_pass(
                state, batch, quota, ck, cn, cfg, rounds=rounds)
            return a, st, q, est, ck, cn, cs

        # koordlint: shape[state: TxNxR i32, batch: TxP i32, quota: TxQ i32]
        def program(state, batch, quota, cfg):
            # cfg broadcasts over the tenant axis (in_axes=None) — one
            # shared ScoringConfig, exactly the serial entries' shape
            return jax.vmap(
                one_tenant,
                in_axes=(0, 0, 0 if has_quota else None, None))(
                    state, batch, quota, cfg)

        fn = jax.jit(program, donate_argnums=(0,))
        self._batched_fns[key] = fn
        return fn

    def _batch_floor(self, limits: dict[str, int]) -> int:
        """Common PodBatch capacity for this cycle: the bucket of the
        largest per-tenant admission (stacking needs equal pod axes).
        Reads the demand snapshot _admission_limits took under the
        tenant locks, so the floor and the limits describe the same
        queue state."""
        from koordinator_tpu.state.cluster_state import _bucket

        worst = 1
        for t in self._tenants.values():
            demand = self._cycle_demand.get(t.name, 0)
            limit = limits.get(t.name)
            worst = max(worst,
                        demand if limit is None else min(demand, limit))
        return _bucket(max(worst, 1), minimum=16)

    def _batched_eligible(self, pairs) -> bool:
        """Shape-alignment gate for the tenant-axis program.  Any miss
        falls back to the pipelined path — a correctness-neutral choice
        (both paths are bit-identical per tenant)."""
        live = [(t, h) for t, h in pairs if not h.done]
        if len(live) < 2:
            return False
        sched0 = live[0][0].scheduler
        caps = set()
        pcaps = set()
        qshapes = set()
        for t, h in live:
            sched = t.scheduler
            if (h.gang_index or h.batch.selector_mask is None
                    or len(sched.reservations)
                    or len(h.pods) < sched.batch_solver_threshold
                    or sched.degraded
                    # the chaos seam fires in _round_dispatch, which the
                    # batched program bypasses — a fault-injected tenant
                    # must keep the per-tenant dispatch path
                    or sched.faults is not None
                    # quality-mode tenants are eligible too (ISSUE 19
                    # closed the PR 13 gap): escalated tenants solve in
                    # their OWN vmapped lp_pack_assign program, the rest
                    # in the select+pass1 program — see
                    # _dispatch_tenant_axis's partition
                    # a forecast-mode tenant charges its admission
                    # reserve in _round_dispatch, which the batched
                    # select+pass1 program bypasses — its cycle keeps
                    # the per-tenant dispatch path (same reasoning as
                    # quality mode)
                    or (sched.forecast_mode != "off"
                        and sched.forecast_plane is not None)
                    # the batched program is the single-device one
                    or self.kit.sharding_active_for(
                        sched.snapshot.capacity)
                    # a tenant with a device plane keeps the per-tenant
                    # dispatch: its solve carries the device stage, the
                    # tenant-axis program stacks states without one
                    or sched.snapshot.resident_state.devices is not None):
                return False
            # the ONE batched program broadcasts tenant 0's config over
            # the tenant axis: every live tenant must share it (by
            # IDENTITY — add_tenant hands tenants a shared default), or
            # its slice would be solved with someone else's scoring and
            # break per-tenant bit-identity.  The solve parameters are
            # the shared kit's, so they cannot differ.
            if sched.config is not sched0.config:
                return False
            caps.add(sched.snapshot.capacity)
            pcaps.add(h.batch.capacity)
            qshapes.add(None if h.quota is None
                        else tuple(h.quota.chain.shape))
        return len(caps) == 1 and len(pcaps) == 1 and len(qshapes) == 1

    def _cycle_batched(self, order, limits, results) -> str:
        """Try the tenant-axis batched program; fall back to the
        pipelined dispatch when the cycle isn't shape-aligned."""
        from koordinator_tpu import tracing

        floor = self._batch_floor(limits)
        held: list[Tenant] = []
        for t in order:
            self._begin_round(t, limits)
            held.append(t)
            t.scheduler.batch_capacity_floor = floor

        def commit(t: Tenant, handle) -> None:
            try:
                results[t.name] = t.scheduler.round_host(handle)
            finally:
                self._end_round(t)
                held.remove(t)

        pairs: list = []
        mode = "batched"
        try:
            for t in order:
                sched = t.scheduler
                # same blanket round_device wears on the per-tenant
                # path: typed segments inside win the sweep, the
                # prepare glue stops reading as unattributed
                with timeline.RECORDER.section(
                        "host_other", "round.prepare", t.name):
                    sched._round_begin()
                    handle = sched._round_prepare()
                handle.start_wall = time.time()
                handle.t0 = time.perf_counter()
                pairs.append((t, handle))
            if self._batched_eligible(pairs):
                self._dispatch_tenant_axis(pairs)
                with timeline.RECORDER.section("host_other",
                                               "round.publish"):
                    for t, handle in pairs:
                        self._account_round(t, handle)
                        if (t.scheduler._round_recordable
                                and not handle.done):
                            t.scheduler._round_flight_record(
                                handle.result, "", handle.start_wall,
                                time.perf_counter() - handle.t0,
                                t.scheduler._current_path(), half="solve")
                for t, handle in pairs:
                    commit(t, handle)
            else:
                # dispatch each prepared round individually and commit
                # depth-1 pipelined (same overlap, per-tenant programs)
                mode = "pipelined"
                pending: collections.deque = collections.deque()
                for t, handle in pairs:
                    with tracing.TRACER.span(
                            "scheduler.round.solve", service="scheduler",
                            attributes={"tenant": t.name}) as span:
                        handle = t.scheduler._round_dispatch(handle)
                    self._account_round(t, handle)
                    if (t.scheduler._round_recordable
                            and not handle.done):
                        t.scheduler._round_flight_record(
                            handle.result, span.trace_id,
                            handle.start_wall,
                            time.perf_counter() - handle.t0,
                            t.scheduler._current_path(), half="solve")
                    pending.append((t, handle))
                    while len(pending) > 1:
                        commit(*pending.popleft())
                while pending:
                    commit(*pending.popleft())
        finally:
            # exception cleanup: a DISPATCHED round still commits (its
            # solve already charged device-side accounting — dropping
            # it would strand phantom placements); an undispatched one
            # decided nothing (the stacked program consumed only a
            # stacking COPY) and just releases its lock
            for t in list(held):
                handle = next((h for tt, h in pairs if tt is t), None)
                dispatched = handle is not None and (
                    handle.done or handle.assignments is not None)
                try:
                    if dispatched:
                        commit(t, handle)
                    else:
                        self._end_round(t)
                        held.remove(t)
                except Exception:  # noqa: BLE001 — already unwinding
                    if t in held:
                        held.remove(t)
                        try:
                            self._end_round(t)
                        except RuntimeError:
                            pass
        return mode

    @staticmethod
    def _wants_quality(sched) -> bool:
        """Mirror of _round_dispatch's use_quality predicate for the
        tenant-axis partition (gang rounds and forecast tenants never
        reach here — _batched_eligible already falls back on them)."""
        return (sched.quality_mode == "lp"
                or (sched.quality_mode == "auto"
                    and sched._quality_escalate))

    def _dispatch_tenant_axis(self, pairs) -> None:
        """ONE vmapped select+pass1 dispatch over every live tenant's
        stacked state — the leading tenant axis the issue names.
        Quality-escalated tenants (ISSUE 19) dispatch through their own
        vmapped lp_pack_assign program in the same window, so a mixed-
        quality fleet no longer serializes its host halves."""
        live = [(t, h) for t, h in pairs if not h.done]
        plain = [(t, h) for t, h in live
                 if not self._wants_quality(t.scheduler)]
        quality = [(t, h) for t, h in live
                   if self._wants_quality(t.scheduler)]
        # timeline observatory (ISSUE 18): the stack/trace/unstack walls
        # of the one vmapped program are solver dispatch, exactly like
        # the per-tenant _round_dispatch window, and the async solve
        # starts executing inside it — its start is the device-busy
        # leading edge each tenant's block pairs with
        dispatch_t0 = timeline.RECORDER.open("tenant_axis.dispatch")
        try:
            if plain:
                self._dispatch_tenant_axis_inner(plain)
            if quality:
                self._dispatch_quality_axis_inner(quality)
        finally:
            timeline.RECORDER.close(dispatch_t0, "dispatch")
            if dispatch_t0:
                for t, _ in live:
                    if t.scheduler._tl_device_t0 is None:
                        t.scheduler._tl_device_t0 = dispatch_t0

    def _dispatch_tenant_axis_inner(self, live) -> None:
        from koordinator_tpu.ops import batch_assign as ba

        # every live tenant's round lock is held (_acquire): the reads
        # fold what each tenant has pending
        states = [t.scheduler.snapshot.state for t, _ in live]
        batches = [h.batch for _, h in live]
        quotas = [h.quota for _, h in live]
        has_quota = quotas[0] is not None
        cfg = live[0][0].scheduler.config
        fn = self._batched_fn((self.kit.method, self.kit.rounds, has_quota))
        stacked_state = self._stack(states)
        stacked_batch = self._stack(batches)
        stacked_quota = self._stack(quotas) if has_quota else None
        a, st, q, est, ck, cn, cs = fn(
            stacked_state, stacked_batch, stacked_quota, cfg)
        for i, (t, handle) in enumerate(live):
            cache = ba.CandidateCache(
                self._unstack(ck, i), self._unstack(cn, i),
                self._unstack(cs, i))
            t.scheduler.round_adopt_batched(
                handle,
                self._unstack(a, i), self._unstack(st, i),
                self._unstack(q, i) if has_quota else None,
                self._unstack(est, i), cache)

    def _quality_batched_fn(self, has_quota: bool):
        """The jitted quality tenant-axis program: vmap of the full
        lp_pack_assign solve (default static iteration knobs, exactly
        the standalone quality branch's call).  The stacked state is
        donated — a stacking COPY, same contract as _batched_fn."""
        fn = self._quality_fns.get(has_quota)
        if fn is not None:
            return fn
        from koordinator_tpu.quality.lp_pack import lp_pack_assign

        def one_tenant(state, batch, quota, cfg):
            return lp_pack_assign(state, batch, cfg, quota)

        # koordlint: shape[state: TxNxR i32, batch: TxP i32, quota: TxQ i32]
        def program(state, batch, quota, cfg):
            return jax.vmap(
                one_tenant,
                in_axes=(0, 0, 0 if has_quota else None, None))(
                    state, batch, quota, cfg)

        fn = jax.jit(program, donate_argnums=(0,))
        self._quality_fns[has_quota] = fn
        return fn

    def _dispatch_quality_axis_inner(self, live) -> None:
        states = [t.scheduler.snapshot.state for t, _ in live]
        batches = [h.batch for _, h in live]
        quotas = [h.quota for _, h in live]
        has_quota = quotas[0] is not None
        cfg = live[0][0].scheduler.config
        # pre-solve slack per tenant (the quality_slack_recovered
        # baseline), dispatched against the ORIGINAL state buffers
        # before the donating program consumes the stacking copy —
        # the standalone quality branch's ordering
        slacks = [t.scheduler._slack_sums(state)
                  for (t, _), state in zip(live, states)]
        fn = self._quality_batched_fn(has_quota)
        a, st, q, qiters = fn(
            self._stack(states), self._stack(batches),
            self._stack(quotas) if has_quota else None, cfg)
        for i, (t, handle) in enumerate(live):
            t.scheduler.round_adopt_quality_batched(
                handle,
                self._unstack(a, i), self._unstack(st, i),
                self._unstack(q, i) if has_quota else None,
                self._unstack(qiters, i), slacks[i])

    # -- surfaces ------------------------------------------------------------

    def tenants_report(self) -> dict:
        """The /debug/tenants body (served by ``debug_tenants_body`` on
        both HTTP surfaces through any tenant's scheduler)."""
        tenants = []
        wsum = sum(max(t.spec.weight, 0.0)
                   for t in self._tenants.values()) or 1.0
        admitted_cycle = sum(t.last_admitted
                             for t in self._tenants.values())
        for t in self._tenants.values():
            sched = t.scheduler
            with sched.lock:
                doc = {
                    "name": t.name,
                    "weight": t.spec.weight,
                    "share_target": max(t.spec.weight, 0.0) / wsum,
                    "share_observed": (
                        t.last_admitted / admitted_cycle
                        if admitted_cycle else 0.0),
                    "credit": round(t.credit, 3),
                    "admitted_last_cycle": t.last_admitted,
                    "admitted_total": t.admitted_total,
                    "overflow_last_round": sched.last_overflow,
                    "rounds": t.rounds,
                    "pending": len(sched.pending),
                    "bound": len(sched.bound),
                    "degraded": sched.degraded,
                    "suspended": sched.last_suspended,
                    "staleness_s": sched._last_staleness_s,
                    "last_solve_path": sched.last_solve_path,
                    "node_capacity": sched.snapshot.capacity,
                    "nodes": len(sched.snapshot.node_index),
                }
            tenants.append(doc)
        return {
            "tenants": tenants,
            "cycle": {
                "seq": self.cycle_seq,
                "mode": self.last_mode,
                "pod_budget": self.cycle_pod_budget,
                "duration_s": self.last_cycle_s,
                "host_wait_fraction": self.last_host_wait_fraction,
                "pipeline": self.pipeline,
                "batch_tenant_axis": self.batch_tenant_axis,
            },
            "kit": {
                "shards": self.kit.shards,
                "mesh": self.kit.mesh is not None,
            },
        }
