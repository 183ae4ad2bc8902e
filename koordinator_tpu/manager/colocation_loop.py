"""The §3.2 colocation loop's manager leg, over the wire.

Reference shape (closed binary-to-binary here the way the reference
closes it through the apiserver):

    koordlet:   NodeMetric usage  -> apiserver       (here: sidecar
                                                      node_usage frames)
    manager:    noderesource_controller.go:71 Reconcile
                -> plugins/batchresource/plugin.go:188
                -> PATCH node.status.allocatable[batch-cpu...]
                                                     (here: a
                                                      node_allocatable
                                                      push)
    scheduler:  informer picks up the new allocatable -> BE pods
                schedule against it                  (here: the
                                                      SchedulerBinding
                                                      applies the delta
                                                      to device rows)

:class:`ManagerSyncBinding` is the manager's informer view: a deltasync
binding that tracks every node's base capacity and the koordlet-reported
usage vectors.  :class:`ColocationLoop` turns that view into
:class:`NodeRecord` rows, runs the batched reconcile
(manager/noderesource_controller.py), and pushes a tick's patches back
as runs of ``node_allocatable`` events — the merge event that cannot
clobber the koordlet's device inventory the way a full node_upsert
would.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Callable, Iterable, Optional

import numpy as np

from koordinator_tpu import metrics, timeline
from koordinator_tpu.api import crds
from koordinator_tpu.api.resources import NUM_RESOURCE_DIMS, ResourceDim
from koordinator_tpu.manager.noderesource_controller import (
    NodeRecord,
    NodeResourceController,
)
from koordinator_tpu.transport.wire import STATE_PUSH_RUN_MAX, FrameType

MIB = 1 << 20

#: the columns a noderesource patch writes, in NodePatch's field order
_PATCHED_DIMS = [int(ResourceDim.BATCH_CPU), int(ResourceDim.BATCH_MEMORY),
                 int(ResourceDim.MID_CPU), int(ResourceDim.MID_MEMORY)]


def sidecar_push(client) -> Callable[[list[str], np.ndarray], list]:
    """The loop's ``push_fn`` over a ReconnectingSidecarClient: one
    run-form ``node_allocatable`` STATE_PUSH a call, answered when every
    event of the frame is committed and applied.  Returns the names the
    sidecar rejected; where it says the watch view is stale
    (``resync``), re-HELLOs first."""

    def push(names, allocatable):
        _, doc, _ = client.call(
            FrameType.STATE_PUSH,
            {"kind": "node_allocatable", "names": names},
            {"allocatable": np.asarray(allocatable, np.int32)})
        if doc.get("resync"):
            client.resync()
        return doc.get("rejected") or []

    return push


class _NodeView:
    __slots__ = ("allocatable", "labels", "annotations", "usage",
                 "sys_usage", "hp_usage", "hp_request", "hp_max_used_req",
                 "usage_time")

    def __init__(self):
        self.allocatable: Optional[np.ndarray] = None
        self.labels: dict = {}
        self.annotations: dict = {}
        self.usage: Optional[np.ndarray] = None
        self.sys_usage: Optional[np.ndarray] = None
        self.hp_usage: Optional[np.ndarray] = None
        #: HP (Prod+Mid) pod REQUEST sum and per-pod max(request, usage)
        #: sum — the request/maxUsageRequest calculate policies' inputs;
        #: without them a wire-fed record computes batch capacity as if
        #: no HP pod had requested anything
        self.hp_request: Optional[np.ndarray] = None
        self.hp_max_used_req: Optional[np.ndarray] = None
        self.usage_time: float = 0.0


class ManagerSyncBinding:
    """Manager-side deltasync binding (the watch half of the loop).

    Only node events matter to the noderesource reconcile; pod and
    reservation events are accepted and dropped (the binding contract
    requires every handler).  Thread-safety: deltas apply on the
    RpcClient reader thread while ``ColocationLoop.tick`` reads on the
    caller's — one lock, same discipline as SchedulerBinding.
    """

    #: service attribution for sync-apply spans (deltasync
    #: _dispatch_event): a traced pod/node event applying here shows up
    #: as the MANAGER's hop in the pod's end-to-end trace
    service_name = "manager"

    def __init__(self, clock=time.time):
        self.clock = clock
        self.lock = threading.Lock()
        self.nodes: dict[str, _NodeView] = {}
        #: NodeRecord instances persist across ticks: the controller's
        #: diff-threshold suppression lives in last_batch_* fields
        self.records: dict[str, NodeRecord] = {}

    def reset(self) -> None:
        with self.lock:
            self.nodes.clear()
            self.records.clear()

    @contextlib.contextmanager
    def _watched(self, n: int = 1):
        """``n`` node deltas applied to the view: a ``colo.watch`` span
        of that many members and a count, under the binding's lock."""
        t0 = timeline.RECORDER.open("colo.watch")
        try:
            with self.lock:
                yield
        finally:
            timeline.RECORDER.close(t0, "deltasync_apply", n=n)
            metrics.colocation_watch_events_total.inc(float(n))

    def _merge_usage(self, view: _NodeView, entry: dict,
                     arrs: dict) -> None:
        """ONE copy of the usage-field merge for live node_usage deltas
        AND the merged arrays a bootstrap snapshot replays inside
        node_upsert — a field added to one path but not the other would
        silently desynchronize replayed records from live ones (the
        hp_request/hp_max_used_req lockstep edit that motivated this).

        Dates the usage by the KOORDLET's report time when the doc
        carries one: stamping apply-time would make a stale node look
        fresh for a whole degrade window after a manager restart +
        snapshot replay.  Explicit None check — a report_time of 0.0 is
        a valid (infinitely stale) timestamp, not an absent one."""
        view.usage = np.asarray(arrs["usage"], np.int32)
        for field in ("sys_usage", "hp_usage", "hp_request",
                      "hp_max_used_req"):
            if field in arrs:
                setattr(view, field, np.asarray(arrs[field], np.int32))
        report_time = entry.get("usage_time")
        view.usage_time = (float(report_time) if report_time is not None
                           else self.clock())

    def node_upsert(self, entry: dict, arrs: dict) -> None:
        with self._watched():
            view = self.nodes.setdefault(entry["name"], _NodeView())
            view.allocatable = np.asarray(arrs["allocatable"], np.int32)
            view.labels = dict(entry.get("labels", {}))
            view.annotations = dict(entry.get("annotations") or {})
            # a bootstrap snapshot replays merged node_usage arrays
            # inside the upsert — dropping them here would compute
            # HP.Used/System as 0 after a manager restart and
            # over-advertise batch capacity for a report interval
            if "usage" in arrs:
                self._merge_usage(view, entry, arrs)
            # an upsert REPLACES the stored doc wholesale, wiping batch
            # dims from the scheduler's allocatable — the record's
            # diff-suppression state must not survive it, or the
            # controller would suppress the re-push (old == new) and
            # leave batch capacity at 0 until usage drifts
            self.records.pop(entry["name"], None)

    def node_usage(self, entry: dict, arrs: dict) -> None:
        with self._watched():
            view = self.nodes.get(entry["name"])
            if view is None:
                return
            self._merge_usage(view, entry, arrs)

    def node_alloc(self, entry: dict, arrs: dict) -> None:
        self.node_alloc_run([(entry, arrs)])

    def node_alloc_run(self, items: list) -> None:
        """Our own patches echo back as deltas, a pushed frame's as one
        run: applied under one hold of the lock and one ``colo.watch``
        span.  Base capacity dims (CPU/MEMORY) are untouched by the
        batch/mid patch, so applying the echo cannot feed back into the
        formula."""
        with self._watched(len(items)):
            for entry, arrs in items:
                view = self.nodes.get(entry["name"])
                if view is not None:
                    view.allocatable = np.asarray(arrs["allocatable"],
                                                  np.int32)

    def node_remove(self, name: str) -> None:
        with self._watched():
            self.nodes.pop(name, None)
            self.records.pop(name, None)

    # non-node events: the reconcile does not consume them
    def node_devices(self, entry: dict) -> None:
        pass

    def pod_add(self, entry: dict, arrs: dict) -> None:
        pass

    def pod_remove(self, name: str) -> None:
        pass

    def reservation_upsert(self, entry: dict, arrs: dict) -> None:
        pass

    def reservation_remove(self, name: str) -> None:
        pass


class ColocationLoop:
    """view -> NodeRecords -> batched reconcile -> node_allocatable push.

    ``push_fn(names, allocatable)`` is the transport seam: a run of
    patches in patch order, ``allocatable`` of shape ``(n,
    NUM_RESOURCE_DIMS)`` with ``n <= STATE_PUSH_RUN_MAX``; it returns
    when every patch it does not report is committed, and reports the
    others as ``(name, reason)`` pairs (or nothing).  The manager binary
    wires it to one run-form STATE_PUSH call on its sidecar client
    (:func:`sidecar_push`); tests can call the service directly.
    Tick-driven like the koordlet's Daemon — the shell owns the cadence
    (``run`` is the convenience loop for real deployments)."""

    def __init__(self, controller: NodeResourceController,
                 binding: ManagerSyncBinding,
                 push_fn: Callable[[list[str], np.ndarray],
                                   Optional[Iterable]],
                 ensure_fn: Optional[Callable[[], object]] = None,
                 forecast=None):
        self.controller = controller
        self.binding = binding
        self.push_fn = push_fn
        #: reconnect seam: called at tick start so a dead watch
        #: connection heals even on ticks that push nothing (the push
        #: path alone would only reconnect when a patch fires)
        self.ensure_fn = ensure_fn
        #: predictive-colocation seam (ISSUE 15): a
        #: forecast.colocation.PredictiveColocation that raises each
        #: record's HP peak to the plane's prediction before the
        #: reconcile, so the pushed batch/mid allocatable shrinks ahead
        #: of the forecast LS ramp.  None (the default) reconciles
        #: byte-identically to the reactive loop.
        self.forecast = forecast
        self.ticks = 0
        self.push_failures = 0
        self.connect_failures = 0
        self._stop = threading.Event()

    def _build_records(self) -> list[NodeRecord]:
        t0 = timeline.RECORDER.open("colo.records")
        records: list[NodeRecord] = []
        try:
            self._fill_records(records)
        finally:
            timeline.RECORDER.close(t0, "host_other", n=len(records))
        return records

    def _fill_records(self, records: list[NodeRecord]) -> None:
        cpu, mem = int(ResourceDim.CPU), int(ResourceDim.MEMORY)
        with self.binding.lock:
            for name, view in self.binding.nodes.items():
                if view.allocatable is None:
                    continue
                record = self.binding.records.get(name)
                if record is None:
                    record = self.binding.records[name] = NodeRecord(
                        name=name, cpu_capacity_milli=0,
                        mem_capacity_mib=0)
                record.cpu_capacity_milli = int(view.allocatable[cpu])
                record.mem_capacity_mib = int(view.allocatable[mem])
                record.labels = dict(view.labels)
                record.annotations = dict(view.annotations)
                usage = (view.usage if view.usage is not None
                         else np.zeros_like(view.allocatable))
                sys_u = (view.sys_usage if view.sys_usage is not None
                         else np.zeros_like(usage))
                record.metric = (None if view.usage is None
                                 else crds.NodeMetricStatus(
                                     update_time=view.usage_time,
                                     node_usage=crds.ResourceUsage(
                                         cpu_milli=int(usage[cpu]),
                                         memory_bytes=int(usage[mem]) * MIB),
                                     system_usage=crds.ResourceUsage(
                                         cpu_milli=int(sys_u[cpu]),
                                         memory_bytes=int(sys_u[mem]) * MIB),
                                 ))
                hp = view.hp_usage
                record.hp_used_cpu_milli = (
                    None if hp is None else int(hp[cpu]))
                record.hp_used_mem_mib = (
                    None if hp is None else int(hp[mem]))
                # request/maxUsageRequest policy inputs: wire-fed records
                # have no per-pod NodeMetric rows, so the aggregates ride
                # the node_usage report (0 when the koordlet predates them
                # — the old over-advertising behavior, explicit here)
                hp_req = view.hp_request
                record.hp_request_cpu_milli = (
                    0 if hp_req is None else int(hp_req[cpu]))
                record.hp_request_mem_mib = (
                    0 if hp_req is None else int(hp_req[mem]))
                hp_max = view.hp_max_used_req
                record.hp_max_used_req_cpu_milli = (
                    0 if hp_max is None else int(hp_max[cpu]))
                record.hp_max_used_req_mem_mib = (
                    0 if hp_max is None else int(hp_max[mem]))
                records.append(record)
        if self.forecast is not None:
            # outside the binding lock: the records are host-local by
            # now, and the plane holds its own lock for the host copy
            for record in records:
                self.forecast.apply(record)

    def tick(self) -> int:
        """One reconcile round; returns the number of patches pushed.

        Runs inside a ``manager.colocation_tick`` trace span; every
        pushed frame gets a ``manager.colocation_push`` child whose
        context rides the STATE_PUSH frame to the sidecar (the RPC
        client injects the active context) and is stamped on every
        event of the frame, so a scheduler can see WHICH manager tick
        changed a node's batch allocatable."""
        from koordinator_tpu import tracing

        self.ticks += 1
        with timeline.RECORDER.section("host_other", "colo.tick"), \
                tracing.TRACER.span(
                    "manager.colocation_tick", service="manager",
                    attributes={"tick": self.ticks}) as tick_span:
            pushed = self._tick_traced(tracing)
            tick_span.set_attribute("pushed", pushed)
        return pushed

    def _tick_traced(self, tracing) -> int:
        if self.ensure_fn is not None:
            try:
                self.ensure_fn()
            except Exception:  # noqa: BLE001 — sidecar down: reconcile
                # over the frozen view anyway, retry next tick
                self.connect_failures += 1
                metrics.colocation_connect_failures_total.inc()
        records = self._build_records()
        patches = self.controller.reconcile(records)
        with timeline.RECORDER.section("host_other", "colo.push",
                                       n=len(patches)):
            return self._push(patches, tracing)

    def _push(self, patches, tracing) -> int:
        """The tick's patches as rows in patch order, handed on in
        frames of at most STATE_PUSH_RUN_MAX, each one synchronous
        ``push_fn`` call.  One patch is a run of one."""
        names, rows = self._patch_rows(patches)
        pushed = 0
        for lo in range(0, len(names), STATE_PUSH_RUN_MAX):
            hi = lo + STATE_PUSH_RUN_MAX
            pushed += self._push_frame(names[lo:hi], rows[lo:hi], tracing)
        return pushed

    def _patch_rows(self, patches) -> tuple[list[str], np.ndarray]:
        """(names, (n, R) allocatable): each patched node's row of the
        view with the four batch / mid columns written over it; a node
        the view no longer holds is left out."""
        kept, base = [], []
        with self.binding.lock:
            for patch in patches:
                view = self.binding.nodes.get(patch.name)
                if view is None or view.allocatable is None:
                    continue
                kept.append(patch)
                base.append(view.allocatable)
        if not kept:
            return [], np.zeros((0, NUM_RESOURCE_DIMS), np.int32)
        rows = np.array(base, np.int32)
        # int32 at the conversion: a value the state tensors cannot hold
        # raises here, it does not wrap
        rows[:, _PATCHED_DIMS] = np.array(
            [(p.batch_cpu_milli, p.batch_mem_mib, p.mid_cpu_milli,
              p.mid_mem_mib) for p in kept], np.int32)
        return [p.name for p in kept], rows

    def _push_frame(self, names: list[str], rows: np.ndarray,
                    tracing) -> int:
        """One frame; returns how many of its names were committed."""
        try:
            with timeline.RECORDER.section("host_other", "colo.push.frame",
                                           n=len(names)), \
                    tracing.TRACER.span(
                        "manager.colocation_push", service="manager",
                        attributes={"tick": self.ticks, "first": names[0],
                                    "last": names[-1], "n": len(names)}):
                metrics.colocation_push_frames_total.inc()
                rejected = self.push_fn(names, rows)
            failed = [name for name, _reason in rejected or ()]
        except Exception:  # noqa: BLE001 — a wedged sidecar costs
            # this frame, not the loop; the diff state was already
            # stamped, so force a re-sync next tick.  last_degraded
            # must reset too: the degraded-suppression branch in
            # reconcile() checks it INSTEAD of last_batch_cpu, so a
            # dropped zeroing patch would otherwise never retry and
            # the scheduler would keep advertising batch capacity on
            # a node with expired metrics
            failed = names
        # a frame with no reply fails every name in it, a name the
        # sidecar rejected fails alone
        for name in failed:
            record = self.binding.records.get(name)
            if record is not None:
                record.last_batch_cpu = -1
                record.last_degraded = False
                record.last_device_resources = None
        self.push_failures += len(failed)
        metrics.colocation_push_failures_total.inc(float(len(failed)))
        metrics.colocation_patches_total.inc(float(len(names) - len(failed)))
        return len(names) - len(failed)

    def run(self, interval_seconds: float = 60.0) -> None:  # pragma: no cover
        while not self._stop.is_set():
            self.tick()
            self._stop.wait(interval_seconds)

    def stop(self) -> None:
        self._stop.set()
