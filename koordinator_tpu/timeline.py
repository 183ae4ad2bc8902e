"""Per-cycle timeline reconstruction + host-wait attribution (ISSUE 18).

ROADMAP item 5 names the host/wire plane as the speed ceiling, and the
only signal so far was one scalar — ``pipeline_host_wait_fraction``,
the share of a cycle's wall the host spent blocked on device solve
results.  This module is the measurement plane underneath it: every
hot spot on the host path records a typed **segment** (two
``perf_counter`` reads + one deque append), and at the end of each
TenantScheduler cycle (or standalone round) the recorder reconstructs
a gantt of the window, attributes every instant of wall time to a
cause, derives device-idle intervals from the dispatch/block edges,
and names the cycle's **critical path**.

Segment causes (also the attribution priority, highest first — at any
instant the most specific active segment wins):

====================  =====================================================
``device_block``      host blocked in ``jax.block_until_ready`` — by
                      construction this bucket equals
                      ``pipeline_host_wait_fraction`` (same intervals the
                      ``_solve_device_s`` accumulator sums)
``lock_wait``         a tenant's round waiting for the front's round lock
                      (``round_lock.acquire``, ``scheduler/tenancy.py``:
                      the one lock wait that is recorded; ``Scheduler.lock``
                      and the sync service's lock are not)
``json_codec``        wire payload encode/decode (``transport/wire.py``)
``deltasync_apply``   a sync event batch applying onto a binding
``dispatch``          host-side solve dispatch work (``_round_dispatch``)
``build_batch``       the BatchBuild phase (``_build_batch``)
``bind_commit``       the Bind phase (``_commit_bind`` loop)
``host_other``        any other monitor phase (Reservations, Solve's
                      host share, Reserve, Diagnose, PostFilter, ...)
====================  =====================================================

Wall time covered by NO segment lands in the explicit ``unattributed``
residual — the phase-accounting invariant test asserts it stays under
5% of the cycle, so silently untimed host work can never reappear.

``device_busy`` segments are NOT host work: they mark the device
executing between a dispatch edge and its block edge, and only feed
the ``device_idle_fraction`` derivation.  ``rpc_client`` segments (an
``RpcClient.call`` and the ``rpc.wait`` inside it) are a CLIENT's view
of work another thread does; the sweep ignores them too.

**One record per span** (ISSUE 24): start, end, cause, name, tenant,
plus ``parent`` (the span open on the same thread when it was
recorded: :meth:`TimelineRecorder.section` and ``open``/``close`` keep
a thread-local stack, ``add`` takes its top), ``thread``, ``n`` and
``busy_s``.  Back-to-back spans of one name, parent, tenant and thread
are ONE stored record with ``n`` members and ``busy_s`` the members'
exact sum — a 50,000-pod wave is a handful of records.  Back-to-back:
the thread never stood outside every span for more than
:data:`COALESCE_S` in between — for a root span, the next start lies
within 1 ms of the last end; a child's gaps are its parent's other
work (the ``wire.decode`` of 500 frames of 3.7 ms each is one run while
the frames are).  The sweep holds a run's extent only at the density
``busy_s / extent``, so the gaps between members attribute to what
contains them.  What the ring still drops is counted in
``timeline_segments_dropped_total``.

**Windows.**  ``finish_cycle(t0, t1)`` reconstructs the round or cycle
window, and before it the wall since the previous window as a doc of
its own, ``mode="ingest"``: what the program did between rounds
(deltasync applies, frames, releases).  An ingest doc's uncovered wall
is the program standing idle, not a residual — the gauges, the 5%
invariant and ``tools/soak_report.py`` judge ``round``/``cycle`` docs
only.  Every doc carries ``by_name``: ``{name: {n, busy_s, self_s,
wait}}``, self time = busy minus what the span's children on the same
thread cover; :data:`WAIT_NAMES` are waits, never summed as work.

**Waits** (ISSUE 34).  A span says what a thread was doing; a **wait
observation** says how long a piece of work stood in a queue before a
thread took it: ``RECORDER.wait(name, t_queued, t_taken, n)``, made by
the thread that takes the work off the queue, both stamps on
``perf_counter``.  Observed today: ``rpc.inbox.<REQUEST TYPE>`` (a frame
between the connection's reader and its dispatch worker),
``rpc.outbox.<FRAME TYPE>`` (a reply, a ready DELTA or a push notice
between ``_Conn.send`` and the connection's sender thread;
``transport/channel.py``).  Every doc carries ``waits``: ``{name: {n,
wait_s, max_s}}`` over the observations whose ``t_taken`` lies in the
doc's wall.  A wait is NOT what the host was doing, so it never enters
``segments``, ``by_name``, the sweep or the critical path (the
benchmark names a device's idle gaps by the shortest segment over
them).  The two waits that are spans, ``rpc.wait`` and
``round_lock.acquire``, stay spans.  What the bounded ring drops before
a window reads it is counted with the records in
``timeline_segments_dropped_total``.

**Attribution semantics.** ``host_wait_attribution{cause}`` decomposes
the WHOLE cycle wall into fractions that sum to 1.0 (including
``unattributed``).  The ``device_block`` bucket equals
``pipeline_host_wait_fraction`` (same clock, same intervals); the
remaining causes decompose its complement — the host share the ROADMAP
item-5 attack has to shrink.

**Kill switch.**  ``KOORD_TIMELINE=0`` in the environment (read once at
import) or ``--no-timeline`` on the scheduler binary disables the
recorder: every hook degrades to one attribute read, no segment is
stored, and scheduling decisions are bit-identical (the instrumentation
is pure host-side timing — it never touches solve inputs either way).

Everything here is stdlib-only and thread-safe: segments arrive from
the cycle thread, RPC reader threads (deltasync applies, wire codec),
and gateway threads concurrently.  ``section()`` additionally enters a
``jax.profiler.TraceAnnotation("koord:<name>")`` when ``jax`` is
already imported (``sys.modules``; this module never imports it), so a
``/debug/profile`` capture shows host spans beside the device ops.
"""

from __future__ import annotations

import contextlib
import os
import sys
import threading
import time
from collections import deque

_perf_counter = time.perf_counter

#: attribution priority, most specific first (see the module docstring)
CAUSES = ("device_block", "lock_wait", "json_codec", "deltasync_apply",
          "dispatch", "build_batch", "bind_commit", "host_other")
#: the residual bucket: wall time no segment covered
UNATTRIBUTED = "unattributed"
#: every label the host_wait_attribution family republishes per cycle
ATTRIBUTION_CAUSES = CAUSES + (UNATTRIBUTED,)
#: device-occupancy marker (feeds device_idle_fraction, not attribution)
DEVICE_BUSY = "device_busy"
#: a client's view of a call another thread serves (``rpc.call.<TYPE>``
#: and its ``rpc.wait``): recorded, never attributed
RPC_CLIENT = "rpc_client"
#: doc mode of the wall between two rounds or cycles
INGEST = "ingest"
#: span names that are waits on another thread's work: flagged in
#: ``by_name`` and never summed as work
WAIT_NAMES = frozenset({"rpc.wait", "round_lock.acquire"})
#: a thread idle (outside every span) for longer than this ends its runs
COALESCE_S = 1e-3

_PRIORITY = {cause: i for i, cause in enumerate(CAUSES)}

#: monitor phase name -> attribution cause (anything unlisted is
#: host_other; the phase name survives on the segment for the gantt)
PHASE_CAUSES = {"BatchBuild": "build_batch", "Bind": "bind_commit"}

# one stored record (a list, so a run extends in place)
(_START, _END, _CAUSE, _NAME, _TENANT, _PARENT, _THREAD, _N, _BUSY, _GEN,
 _EPOCH) = range(11)


class _Node:
    """One position in a thread's span tree (a name under a path of
    open parents) and the running record of the spans recorded there."""

    __slots__ = ("name", "parent", "kids", "rec")

    def __init__(self, name: str, parent: str):
        self.name = name
        self.parent = parent
        self.kids: dict[str, _Node] = {}
        self.rec: list | None = None


class _ThreadState:
    """One thread's span tree, its stack of open spans, and its idle
    epoch: bumped whenever the thread stood outside every span for more
    than :data:`COALESCE_S`, which ends its runs."""

    __slots__ = ("ident", "root", "stack", "epoch", "root_end")

    def __init__(self):
        self.ident = threading.get_ident()
        self.root = _Node("", "")
        self.stack: list[_Node] = [self.root]
        self.epoch = 0
        self.root_end = 0.0


def _merge_intervals(intervals: list[tuple[float, float]]
                     ) -> list[tuple[float, float]]:
    """Union of [start, end) intervals, sorted and coalesced."""
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def sweep_attribution(segments: list[dict], t0: float, t1: float
                      ) -> tuple[dict, list[dict]]:
    """Attribute every instant of [t0, t1] to a cause.

    An event sweep over the segment boundaries: at each instant the
    highest-priority active segment's cause wins (nesting puts the
    specific segment — a block wait inside the Solve phase, a codec
    call inside a deltasync apply — above its container).  A segment
    that is a RUN of back-to-back spans (``busy_s`` below its extent)
    holds its extent only at the density ``busy_s / extent``: the rest
    of each instant flows on to the next priority, so the gaps between
    a run's members attribute to what contains them, not to the run.
    Returns ``(seconds_by_cause, chain)`` where the chain is the
    covering sequence of maximal same-cause intervals — the cycle's
    critical path, since the cycle runs to completion and at every
    instant the chain names what the wall clock was spent on.  This
    runs once per cycle on the scheduling thread, so it is O(n log n)
    in segments, not elementary-intervals x segments.
    """
    totals = {cause: 0.0 for cause in ATTRIBUTION_CAUSES}
    if t1 <= t0:
        return totals, []
    events: list[tuple[float, int, int, str, float]] = []
    for s in segments:
        prio = _PRIORITY.get(s["cause"])
        if prio is None:
            continue
        start, end = max(s["start"], t0), min(s["end"], t1)
        if end <= start:
            continue
        extent = s["end"] - s["start"]
        density = min(s.get("busy_s", extent) / extent, 1.0)
        events.append((start, 1, prio, s["name"], density))
        events.append((end, -1, prio, s["name"], density))
    events.sort(key=lambda e: e[0])
    counts = [0] * len(CAUSES)
    density_of = [0.0] * len(CAUSES)
    names: list[list[str]] = [[] for _ in CAUSES]
    chain: list[dict] = []

    def emit(lo: float, hi: float) -> None:
        if hi <= lo:
            return
        left, best = 1.0, None
        for p, active in enumerate(counts):
            if not active:
                continue
            if best is None:
                best = p
            share = left * min(density_of[p], 1.0)
            totals[CAUSES[p]] += share * (hi - lo)
            left -= share
            if left <= 0.0:
                break
        totals[UNATTRIBUTED] += max(left, 0.0) * (hi - lo)
        if best is None:
            cause, name = UNATTRIBUTED, ""
        else:
            cause, name = CAUSES[best], names[best][-1]
        if chain and chain[-1]["cause"] == cause:
            chain[-1]["end"] = hi
        else:
            chain.append({"start": lo, "end": hi,
                          "cause": cause, "name": name})

    prev = t0
    i, n = 0, len(events)
    while i < n:
        now = events[i][0]
        emit(prev, now)
        while i < n and events[i][0] == now:
            _, delta, prio, name, density = events[i]
            if delta > 0:
                counts[prio] += 1
                density_of[prio] += density
                names[prio].append(name)
            else:
                counts[prio] -= 1
                # an empty level reads exactly 0, whatever the float sum
                density_of[prio] = (density_of[prio] - density
                                    if counts[prio] else 0.0)
                names[prio].remove(name)
            i += 1
        prev = now
    emit(prev, t1)
    return totals, chain


def device_idle(segments: list[dict], t0: float, t1: float
                ) -> tuple[list[tuple[float, float]], float]:
    """Idle intervals = the cycle window minus the union of
    ``device_busy`` spans (each one a dispatch edge to its block
    edge).  Returns ``(idle_intervals, busy_seconds)``."""
    busy = _merge_intervals([
        (max(s["start"], t0), min(s["end"], t1)) for s in segments
        if s["cause"] == DEVICE_BUSY and s["end"] > t0 and s["start"] < t1])
    idle: list[tuple[float, float]] = []
    cursor = t0
    for s, e in busy:
        if s > cursor:
            idle.append((cursor, s))
        cursor = max(cursor, e)
    if cursor < t1:
        idle.append((cursor, t1))
    return idle, sum(e - s for s, e in busy)


def by_name(segments: list[dict]) -> dict:
    """``{name: {n, busy_s, self_s, wait}}`` over clipped segments:
    busy is the members' sum, self time is busy minus what the span's
    children (``parent`` == its name) on the same thread cover."""
    own: dict[tuple[int, str], list[float]] = {}
    children: dict[tuple[int, str], float] = {}
    for s in segments:
        if s["cause"] == DEVICE_BUSY:
            continue
        slot = own.setdefault((s["thread"], s["name"]), [0.0, 0.0])
        slot[0] += s["n"]
        slot[1] += s["busy_s"]
        if s["parent"]:
            key = (s["thread"], s["parent"])
            children[key] = children.get(key, 0.0) + s["busy_s"]
    out: dict[str, dict] = {}
    for (thread, name), (n, busy) in own.items():
        doc = out.setdefault(name, {"n": 0.0, "busy_s": 0.0, "self_s": 0.0,
                                    "wait": name in WAIT_NAMES})
        doc["n"] += n
        doc["busy_s"] += busy
        doc["self_s"] += max(busy - children.get((thread, name), 0.0), 0.0)
    return out


class TimelineRecorder:
    """Span sink + per-window reconstruction ring.

    One module-level instance (:data:`RECORDER`) serves every
    scheduler in the process — segments carry a tenant tag, windows
    clip by time, and the ring backs ``/debug/timeline``.
    """

    def __init__(self, enabled: bool = True, max_segments: int = 16384,
                 max_cycles: int = 64, max_waits: int = 65536):
        self._enabled = enabled
        self._lock = threading.Lock()
        self._segments: deque = deque(maxlen=max_segments)
        #: wait observations no window has read: (name, t_queued,
        #: t_taken, n).  Appended and popped without the lock (both are
        #: atomic on a deque); never iterated
        self._waits: deque = deque(maxlen=max_waits)
        self._cycles: deque = deque(maxlen=max_cycles)
        self._tls = threading.local()
        #: bumped by every finish_cycle: a record of an older generation
        #: was handed to a window and is never extended again
        self._gen = 0
        #: end of the newest reconstructed window (None until the first)
        self._last_end: float | None = None
        #: records the full ring pushed out before any window read them
        self.dropped = 0

    # -- the hot-path surface -------------------------------------------

    @property
    def enabled(self) -> bool:
        return self._enabled

    def set_enabled(self, enabled: bool) -> None:
        """The kill switch: disabling drops pending segments so a
        re-enable can't attribute a stale window."""
        self._enabled = bool(enabled)
        with self._lock:
            self._forget()

    def _forget(self) -> None:  # koordlint: guarded-by(self._lock)
        """Drop what no window has read; no run goes on across it."""
        self._segments.clear()
        self._waits.clear()
        self._gen += 1
        self._last_end = None

    def _thread(self) -> _ThreadState:
        """This thread's state, made on its first span."""
        state = self._tls.state = _ThreadState()
        return state

    def _record(self, st: _ThreadState, node: _Node, start: float,
                end: float, cause: str, tenant: str, n: int,
                merge: bool = True) -> None:
        """One span of ``n`` members at ``node`` of ``st``'s thread:
        extends the running record there, or starts a new one.  A run
        goes on while the thread is never idle (outside every span) for
        more than COALESCE_S: for a root span that is "next start
        within 1 ms of the last end"; a child's gaps are its parent's
        other work."""
        if not node.parent:
            if start - st.root_end > COALESCE_S:
                st.epoch += 1
            st.root_end = end
        rec = node.rec
        if (merge and rec is not None and rec[_GEN] == self._gen
                and rec[_EPOCH] == st.epoch and rec[_TENANT] == tenant):
            # the run goes on: no lock, this thread owns the record
            rec[_END] = end
            rec[_N] += n
            rec[_BUSY] += end - start
            return
        node.rec = rec = [start, end, cause, node.name, tenant, node.parent,
                          st.ident, n, end - start, self._gen, st.epoch]
        with self._lock:
            if len(self._segments) == self._segments.maxlen:
                self._count_drop()
            self._segments.append(rec)

    def _count_drop(self) -> None:  # koordlint: guarded-by(self._lock)
        from koordinator_tpu import metrics

        self.dropped += 1
        metrics.timeline_segments_dropped.inc()

    def add(self, start: float, end: float, cause: str,
            name: str = "", tenant: str = "", n: int = 1,
            merge: bool = True) -> None:
        """Record one finished span (perf_counter timestamps) of ``n``
        members under the span open on this thread.  ``merge=False``
        keeps the span a record of its own: for the few whose exact
        intervals a gauge equals by construction."""
        if not self._enabled or end <= start:
            return
        try:
            st = self._tls.state
        except AttributeError:
            st = self._thread()
        top = st.stack[-1]
        node = top.kids.get(name)
        if node is None:
            node = top.kids[name] = _Node(name, top.name)
        self._record(st, node, start, end, cause, tenant, n, merge)

    def open(self, name: str) -> float:
        """Per-event form of :meth:`section`: push ``name`` as this
        thread's open span and read the clock.  Returns 0.0 when
        disabled; hand the value to :meth:`close`."""
        if not self._enabled:
            return 0.0
        try:
            st = self._tls.state
        except AttributeError:
            st = self._thread()
        stack = st.stack
        top = stack[-1]
        node = top.kids.get(name)
        if node is None:
            node = top.kids[name] = _Node(name, top.name)
        now = _perf_counter()
        if top is st.root and now - st.root_end > COALESCE_S:
            # the thread stood idle: its runs end here, children's too
            st.epoch += 1
            st.root_end = now
        stack.append(node)
        return now

    def close(self, t0: float, cause: str, tenant: str = "",
              n: int = 1) -> None:
        """Pop the span :meth:`open` pushed and record it."""
        if not t0:
            return
        t1 = _perf_counter()
        st = self._tls.state
        node = st.stack.pop()
        if self._enabled and t1 > t0:
            self._record(st, node, t0, t1, cause, tenant, n)

    def wait(self, name: str, t_queued: float, t_taken: float,
             n: int = 1) -> None:
        """Observe that ``n`` pieces of work stood queued from
        ``t_queued`` until this thread took them at ``t_taken``
        (perf_counter stamps; a ``t_queued`` of 0.0 is "no stamp was
        taken": the recorder was off when the work was queued).  One
        append, no lock; never a segment."""
        if not self._enabled or not t_queued:
            return
        waits = self._waits
        if len(waits) == waits.maxlen:
            with self._lock:
                self._count_drop()
        waits.append((name, t_queued, t_taken, n))

    @contextlib.contextmanager
    def section(self, cause: str, name: str = "", tenant: str = "",
                n: int = 1):
        """Time a block as one span of ``n`` members, a parent to what
        it encloses; near-free when disabled."""
        if not self._enabled:
            yield
            return
        with _annotation(name or cause):
            t0 = self.open(name)
            try:
                yield
            finally:
                self.close(t0, cause, tenant, n)

    # -- window reconstruction ------------------------------------------

    def _window(self, t0: float, t1: float) -> list[dict]:
        """The records overlapping [t0, t1], clipped; a straddling
        run's ``n`` and ``busy_s`` are cut pro rata."""
        with self._lock:
            raw = [list(r) for r in self._segments
                   if r[_END] > t0 and r[_START] < t1]
        out = []
        for r in raw:
            start, end = max(r[_START], t0), min(r[_END], t1)
            whole = start == r[_START] and end == r[_END]
            share = 1.0 if whole else (end - start) / (r[_END] - r[_START])
            out.append({"start": start, "end": end, "cause": r[_CAUSE],
                        "name": r[_NAME], "tenant": r[_TENANT],
                        "parent": r[_PARENT], "thread": r[_THREAD],
                        "n": r[_N] if whole else r[_N] * share,
                        "busy_s": r[_BUSY] * share})
        return out

    def _take_waits(self, bounds: list[tuple[float, float]]
                    ) -> list[dict]:
        """One ``waits`` map per window of ``bounds`` (consecutive,
        oldest first): every pending observation goes to the window
        whose wall holds its ``t_taken``; one taken before the first
        window is dropped (nobody will read it), one taken after the
        last stays for the next call."""
        out: list[dict] = [{} for _ in bounds]
        first_lo, last_hi = bounds[0][0], bounds[-1][1]
        ends = [(hi, doc) for (_, hi), doc in zip(bounds, out)]
        pop, later = self._waits.popleft, []
        try:
            while True:
                obs = pop()
                name, t_queued, t_taken, n = obs
                if t_taken > last_hi:
                    later.append(obs)
                    continue
                if t_taken < first_lo:
                    continue
                for hi, doc in ends:
                    if t_taken <= hi:
                        break
                waited = t_taken - t_queued
                slot = doc.get(name)
                if slot is None:
                    doc[name] = [n, waited, waited]
                else:
                    slot[0] += n
                    slot[1] += waited
                    if waited > slot[2]:
                        slot[2] = waited
        except IndexError:
            pass
        self._waits.extend(later)
        return [{name: {"n": n, "wait_s": wait_s, "max_s": max_s}
                 for name, (n, wait_s, max_s) in doc.items()}
                for doc in out]

    def _doc(self, cycle: int, t0: float, t1: float, mode: str,
             waits: dict) -> dict:
        wall = t1 - t0
        segments = self._window(t0, t1)
        totals, chain = sweep_attribution(segments, t0, t1)
        idle, busy_s = device_idle(segments, t0, t1)
        attribution = {c: totals[c] / wall for c in ATTRIBUTION_CAUSES}
        named = {c: s for c, s in totals.items()
                 if c != UNATTRIBUTED and s > 0.0}
        critical_cause = (max(named, key=named.get) if named
                          else UNATTRIBUTED)
        return {
            "cycle": cycle,
            "mode": mode,
            "start": t0,
            "wall_s": wall,
            "segments": [
                dict(s, start=s["start"] - t0, end=s["end"] - t0)
                for s in sorted(segments, key=lambda s: s["start"])],
            "by_name": by_name(segments),
            "waits": waits,
            "attribution": attribution,
            "attribution_s": totals,
            "unattributed_fraction": attribution[UNATTRIBUTED],
            "device_busy_s": busy_s,
            "device_idle_fraction": (wall - busy_s) / wall,
            "device_idle": [(s - t0, e - t0) for s, e in idle],
            "critical_path": [
                {"start": c["start"] - t0, "end": c["end"] - t0,
                 "cause": c["cause"], "name": c["name"]}
                for c in chain],
            "critical_cause": critical_cause,
            "critical_seconds": totals.get(critical_cause, 0.0),
        }

    def finish_cycle(self, cycle: int, t0: float, t1: float,
                     mode: str = "cycle", publish: bool = True) -> dict | None:
        """Reconstruct the window [t0, t1]: clip segments, attribute
        wall time, derive device idle, name the critical path; append
        the cycle doc to the ring and (by default) republish the
        ``host_wait_attribution`` / ``device_idle_fraction`` /
        ``critical_path_seconds`` gauges.  The wall since the previous
        window goes before it as a doc of its own (``mode="ingest"``,
        never published).  Returns the cycle's doc (None when disabled
        or the window is degenerate)."""
        if not self._enabled or t1 <= t0:
            return None
        with self._lock:
            # seal every run: what a window was handed is not extended
            self._gen += 1
            last_end = self._last_end
        bounds = [(t0, t1)]
        if last_end is not None and last_end < t0:
            bounds.insert(0, (last_end, t0))
        waits = self._take_waits(bounds)
        docs = [self._doc(cycle, lo, hi, INGEST, w)
                for (lo, hi), w in zip(bounds[:-1], waits)]
        doc = self._doc(cycle, t0, t1, mode, waits[-1])
        docs.append(doc)
        with self._lock:
            self._cycles.extend(docs)
            # consumed history goes; a record still running past t1
            # stays and the next window takes the rest of it
            kept = [r for r in self._segments if r[_END] > t1]
            self._segments.clear()
            self._segments.extend(kept)
            self._last_end = max(t1, last_end or t1)
        if publish:
            self._publish(doc)
        return doc

    @staticmethod
    def _publish(doc: dict) -> None:
        from koordinator_tpu import metrics

        for cause in ATTRIBUTION_CAUSES:
            # every cause republished each cycle so cleared ones read 0
            metrics.host_wait_attribution.set(
                doc["attribution"][cause], labels={"cause": cause})
            metrics.critical_path_seconds.set(
                doc["attribution_s"][cause], labels={"cause": cause})
        metrics.device_idle_fraction.set(doc["device_idle_fraction"])

    def cycles(self, limit: int = 8) -> list[dict]:
        """Newest-first window docs (the /debug/timeline body): rounds,
        cycles and the ingest windows between them, told apart by
        ``mode``."""
        with self._lock:
            out = list(self._cycles)[-max(limit, 0):]
        out.reverse()
        return out

    def reset_for_tests(self) -> None:
        with self._lock:
            self._forget()
            self._cycles.clear()
            self.dropped = 0


def _annotation(name: str):
    """``jax.profiler.TraceAnnotation("koord:<name>")`` when jax is
    already loaded in the process, else a no-op context."""
    jax = sys.modules.get("jax")
    profiler = getattr(jax, "profiler", None)
    if profiler is None:
        return contextlib.nullcontext()
    return profiler.TraceAnnotation(f"koord:{name}")


#: process-wide recorder; KOORD_TIMELINE=0 disables at import (the env
#: half of the kill switch — --no-timeline is the CLI half)
RECORDER = TimelineRecorder(
    enabled=os.environ.get("KOORD_TIMELINE", "1") != "0")
