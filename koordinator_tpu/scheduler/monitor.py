"""Scheduler monitor: per-round phase timing with slow-round logging.

Equivalent of ``frameworkext/scheduler_monitor.go:44-100`` — records how long
each scheduling phase takes and logs phases that exceed the configured
timeout (the reference logs pods stuck in a phase).

Observability duties (PR 3): each phase is also a trace span (child of
the round span the scheduler opens, when one is active) and feeds the
``scheduling_duration_seconds`` histogram WITH a trace-id exemplar, so a
latency outlier on the dashboard links straight to the round trace that
produced it.  ``start_round()``/``round_timings`` expose the CURRENT
round's per-phase wall times for the flight recorder.
"""

from __future__ import annotations

import contextlib
import logging
import time

from koordinator_tpu import metrics, timeline, tracing

logger = logging.getLogger("koordinator_tpu.scheduler")


class SchedulerMonitor:
    def __init__(self, timeout_sec: float = 1.0,
                 clock=time.perf_counter):
        self.timeout_sec = timeout_sec
        self.clock = clock
        #: per-phase wall times of the round in flight (reset by
        #: start_round; the flight recorder snapshots it at round end)
        self.round_timings: dict[str, float] = {}
        #: tenancy identity (ISSUE 11): when set, every phase
        #: observation additionally carries a {tenant=...} label so the
        #: per-tenant p99 SLO and dashboards can slice one histogram
        self.tenant = ""

    def start_round(self) -> None:
        """Reset the per-round phase accumulator (called by the
        scheduler at round start, under the round lock)."""
        self.round_timings = {}

    @contextlib.contextmanager
    def phase(self, name: str, carry_s: float = 0.0):
        """``carry_s`` folds wall time measured OUTSIDE this context
        into the phase's one observation — the pipelined round split
        times the solve dispatch in the device half and carries it into
        the host half's "Solve" phase, so a round still produces exactly
        one Solve observation (the SLO engine's per-observation bad
        fractions must not dilute)."""
        # phase spans only under an active trace (the scheduler's round
        # span): standalone monitor users pay nothing, traced rounds get
        # one child span per phase
        ctx = tracing.current_context()
        span_cm = (tracing.TRACER.span(f"phase.{name}") if ctx is not None
                   else contextlib.nullcontext())
        # the timeline span is timed on perf_counter directly (not
        # self.clock, which tests may fake): cycle windows clip by real
        # monotonic time and a synthetic clock would mis-place segments.
        # A section, so the spans recorded inside name it as parent
        tl = timeline.RECORDER.section(
            timeline.PHASE_CAUSES.get(name, "host_other"),
            f"phase.{name}", self.tenant)
        start = self.clock()
        try:
            with span_cm, tl:
                yield
        finally:
            elapsed = self.clock() - start + carry_s
            self.round_timings[name] = (
                self.round_timings.get(name, 0.0) + elapsed)
            # feed the prometheus surface too (the reference exports
            # scheduling-cycle latency per phase from the same hook);
            # the exemplar links this observation to the round's trace
            exemplar = ({"trace_id": ctx.trace_id} if ctx is not None
                        else None)
            labels = {"phase": name}
            if self.tenant:
                labels["tenant"] = self.tenant
            metrics.scheduling_latency.observe(
                elapsed, labels=labels, exemplar=exemplar)
            if name == "Solve":
                metrics.solver_batch_latency.observe(
                    elapsed, exemplar=exemplar)
            if elapsed > self.timeout_sec:
                logger.warning(
                    "scheduling phase %s took %.3fs (timeout %.3fs)",
                    name, elapsed, self.timeout_sec,
                )
