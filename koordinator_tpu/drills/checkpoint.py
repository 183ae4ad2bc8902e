"""Warm-restart checkpoints: the scheduler's host-side snapshot + the
deltasync replay cursor, serialized with the wire payload codec.

A restarted (or failed-over) scheduler restores this locally and then
catches up via deltasync DELTAs instead of paying a full snapshot
re-bootstrap: the checkpoint carries ``(rv, instance)`` — the replay
cursor ``StateSyncClient`` sends in its HELLO — so the service answers
with ``log.since(rv)`` when the cursor is within retention (see
docs/wire_protocol.md, "State sync").  Recovery time becomes a bounded,
measurable RTO: restore cost is local deserialization, catch-up cost is
proportional to the *downtime*, not to the cluster.

What is captured (one consistent cut under ``scheduler.lock``):

- every node's ``NodeSpec`` (allocatable/usage/agg/prod, labels,
  taints), in snapshot **row order** so the restored ``ClusterSnapshot``
  assigns identical rows — the save→restore roundtrip is bit-identical
  on the state arrays (tests/test_drills.py proves it);
- the pending queue (full ``PodSpec``s, creation stamps included);
- bound pods (``BoundPod``s; their ``node_generation`` is re-stamped to
  the restored snapshot's generations so a later release decrements the
  instance it was actually charged to);
- gang records and the quota-tree spec (+ per-quota ``used`` recharged
  from the restored bound pods);
- the replay cursor.

What is NOT captured: reservations and fine-grained CPU/device
assignments — both re-enter via their own sync events; a checkpoint
taken while reservations are live records ``reservations_dropped`` so
the caller can elect a full re-bootstrap instead.  Solver state is
device-resident and derived: the restored scheduler's first
``flush()`` rebuilds it from the host arrays, so checkpointing cannot
change any scheduling decision (checkpoints off ⇒ bit-identical
rounds).
"""

from __future__ import annotations

import os
import time

import numpy as np

CHECKPOINT_VERSION = 1


def _stack(vectors, dims: int, dtype) -> np.ndarray:
    if not vectors:
        return np.zeros((0, dims), dtype)
    return np.stack([np.asarray(v, dtype) for v in vectors])


def capture(scheduler, sync=None) -> tuple[dict, dict[str, np.ndarray]]:
    """One consistent cut of the scheduler's host state, as a
    ``(doc, arrays)`` pair for :func:`koordinator_tpu.transport.wire.
    encode_payload`.  Holds ``scheduler.lock`` for the whole walk — the
    checkpoint writer must see no half-applied round (lock-discipline:
    never copy scheduler fields outside the round lock)."""
    from koordinator_tpu.api.resources import NUM_RESOURCE_DIMS

    dims = NUM_RESOURCE_DIMS
    doc: dict = {"version": CHECKPOINT_VERSION}
    arrays: dict[str, np.ndarray] = {}
    with scheduler.lock:
        snap = scheduler.snapshot
        # -- nodes, in row order (identical row assignment on restore)
        names = sorted(snap.node_index, key=snap.node_index.__getitem__)
        nodes = []
        alloc, usage, agg, prod = [], [], [], []
        umask, amask, pmask = [], [], []
        zero = np.zeros(dims, np.int32)
        for name in names:
            spec = snap.node_specs[name]
            nodes.append({"name": name,
                          "labels": dict(spec.labels),
                          "taints": dict(spec.taints)})
            alloc.append(spec.allocatable)
            for vec, out, mask in ((spec.usage, usage, umask),
                                   (spec.agg_usage, agg, amask),
                                   (spec.prod_usage, prod, pmask)):
                mask.append(0 if vec is None else 1)
                out.append(zero if vec is None else vec)
        doc["nodes"] = nodes
        doc["snapshot_capacity"] = int(snap.capacity)
        arrays["node_allocatable"] = _stack(alloc, dims, np.int32)
        arrays["node_usage"] = _stack(usage, dims, np.int32)
        arrays["node_agg_usage"] = _stack(agg, dims, np.int32)
        arrays["node_prod_usage"] = _stack(prod, dims, np.int32)
        arrays["node_usage_mask"] = np.asarray(umask, np.int8)
        arrays["node_agg_mask"] = np.asarray(amask, np.int8)
        arrays["node_prod_mask"] = np.asarray(pmask, np.int8)

        # -- pending queue (arrival order preserved: dict order)
        pend, pend_req = [], []
        for pod in scheduler.pending.values():
            pend.append({
                "name": pod.name, "priority": int(pod.priority),
                "qos": int(pod.qos), "gang": pod.gang,
                "quota": pod.quota,
                "non_preemptible": bool(pod.non_preemptible),
                "node_selector": dict(pod.node_selector),
                "tolerations": dict(pod.tolerations),
                "creation": float(pod.creation),
                "labels": dict(pod.labels), "owner": pod.owner,
                "preemption_policy": pod.preemption_policy,
            })
            pend_req.append(pod.requests)
        doc["pending"] = pend
        arrays["pending_requests"] = _stack(pend_req, dims, np.int32)

        # -- bound pods
        bnd, bnd_req = [], []
        for bp in scheduler.bound.values():
            bnd.append({
                "name": bp.name, "node": bp.node,
                "priority": int(bp.priority), "quota": bp.quota,
                "non_preemptible": bool(bp.non_preemptible),
                "labels": dict(bp.labels), "gang": bp.gang,
            })
            bnd_req.append(bp.requests)
        doc["bound"] = bnd
        arrays["bound_requests"] = _stack(bnd_req, dims, np.int32)

        # -- gangs
        doc["gangs"] = [
            {"name": g.name, "min_member": int(g.min_member),
             "group": g.group,
             "wait_time_sec": (None if g.wait_time_sec is None
                               else float(g.wait_time_sec))}
            for g in scheduler.gangs.values()]

        # -- quota tree (BFS from the root so parents restore first)
        tree = scheduler.quota_tree
        if tree is not None:
            from koordinator_tpu.quota.tree import ROOT

            quotas = []
            qmin, qmax, qsw, qg = [], [], [], []
            frontier = list(tree.children.get(ROOT, ()))
            while frontier:
                name = frontier.pop(0)
                q = tree.nodes[name]
                quotas.append({"name": q.name, "parent": q.parent,
                               "allow_lent": bool(q.allow_lent),
                               "enable_scale_min":
                                   bool(q.enable_scale_min)})
                qmin.append(q.min)
                qmax.append(q.max)
                qsw.append(q.shared_weight)
                qg.append(q.guarantee)
                frontier.extend(tree.children.get(name, ()))
            doc["quotas"] = quotas
            doc["quota_scale_min"] = bool(tree.scale_min_enabled)
            arrays["quota_total"] = np.asarray(tree.total_resource,
                                              np.int64)
            arrays["quota_min"] = _stack(qmin, dims, np.int64)
            arrays["quota_max"] = _stack(qmax, dims, np.int64)
            arrays["quota_shared_weight"] = _stack(qsw, dims, np.int64)
            arrays["quota_guarantee"] = _stack(qg, dims, np.int64)

        # -- replay cursor + limitations
        doc["cursor"] = {
            "rv": int(sync.rv) if sync is not None else -1,
            "instance": sync.instance if sync is not None else None,
        }
        doc["reservations_dropped"] = len(scheduler.reservations.specs())
    return doc, arrays


def restore_into(scheduler, doc: dict,
                 arrays: dict[str, np.ndarray], sync=None) -> dict:
    """Apply a captured checkpoint onto a FRESH scheduler (empty
    snapshot/queues; the caller owns its construction — config, bind_fn,
    solver kit, elector).  Primes ``sync``'s replay cursor so its next
    ``bootstrap()`` HELLO asks for deltas since the checkpoint instead
    of a full snapshot.  Returns restore stats."""
    from koordinator_tpu.quota.tree import QuotaTree
    from koordinator_tpu.scheduler.scheduler import BoundPod, GangRecord
    from koordinator_tpu.scheduler.snapshot import NodeSpec, PodSpec

    if doc.get("version") != CHECKPOINT_VERSION:
        raise ValueError(
            f"checkpoint version {doc.get('version')!r} != "
            f"{CHECKPOINT_VERSION}")

    def row(key, i):
        return np.asarray(arrays[key][i], arrays[key].dtype)

    with scheduler.lock:
        if doc.get("quotas"):
            tree = QuotaTree(np.asarray(arrays["quota_total"], np.int64),
                             scale_min_enabled=bool(
                                 doc.get("quota_scale_min", False)))
            for i, q in enumerate(doc["quotas"]):
                tree.add(q["name"],
                         min=row("quota_min", i),
                         max=row("quota_max", i),
                         parent=q["parent"],
                         shared_weight=row("quota_shared_weight", i),
                         guarantee=row("quota_guarantee", i),
                         allow_lent=bool(q["allow_lent"]),
                         enable_scale_min=bool(q["enable_scale_min"]))
            scheduler.quota_tree = tree
        for i, entry in enumerate(doc.get("nodes", ())):
            scheduler.snapshot.upsert_node(NodeSpec(
                name=entry["name"],
                allocatable=row("node_allocatable", i),
                usage=(row("node_usage", i)
                       if arrays["node_usage_mask"][i] else None),
                agg_usage=(row("node_agg_usage", i)
                           if arrays["node_agg_mask"][i] else None),
                prod_usage=(row("node_prod_usage", i)
                            if arrays["node_prod_mask"][i] else None),
                labels=dict(entry.get("labels", {})),
                taints=dict(entry.get("taints", {})),
            ))
        for g in doc.get("gangs", ()):
            scheduler.register_gang(GangRecord(
                name=g["name"], min_member=int(g["min_member"]),
                group=g.get("group"),
                wait_time_sec=g.get("wait_time_sec")))
    # enqueue/add_bound_pod take the lock themselves (RLock — but keep
    # the public entry points on their own acquire so their accounting
    # stays the single audited path)
    for i, p in enumerate(doc.get("pending", ())):
        scheduler.enqueue(PodSpec(
            name=p["name"], requests=row("pending_requests", i),
            priority=int(p["priority"]), qos=int(p["qos"]),
            gang=p.get("gang"), quota=p.get("quota"),
            non_preemptible=bool(p.get("non_preemptible", False)),
            node_selector=dict(p.get("node_selector", {})),
            tolerations=dict(p.get("tolerations", {})),
            creation=float(p.get("creation", 0.0)),
            labels=dict(p.get("labels", {})), owner=p.get("owner"),
            preemption_policy=p.get("preemption_policy",
                                    "PreemptLowerPriority")))
    with scheduler.lock:
        reserve_by_node: dict[str, np.ndarray] = {}
        for i, b in enumerate(doc.get("bound", ())):
            requests = row("bound_requests", i)
            pod = BoundPod(
                name=b["name"], node=b["node"], requests=requests,
                priority=int(b["priority"]), quota=b.get("quota"),
                non_preemptible=bool(b.get("non_preemptible", False)),
                labels=dict(b.get("labels", {})), gang=b.get("gang"),
                # charge the RESTORED node instance, not the dead one's
                # generation — a later release must decrement the
                # instance this restore is about to reserve on
                node_generation=scheduler.snapshot.node_generation.get(
                    b["node"], 0))
            scheduler.bound[pod.name] = pod
            if pod.node in scheduler.snapshot.node_index:
                prev = reserve_by_node.get(pod.node)
                cur = requests.astype(np.int64)
                reserve_by_node[pod.node] = (
                    cur if prev is None else prev + cur)
            # the bind-path mirror: the node reserve below owns node
            # accounting, the quota charge is the caller's
            # (delete_pod releases both)
            scheduler._charge_quota_used(pod, sign=1)
        # host-pending like every reserve: the whole bound set reaches
        # the device in the one fold of the next read of the state
        scheduler.snapshot.reserve_batch(reserve_by_node)
    if sync is not None:
        cursor = doc.get("cursor") or {}
        sync.rv = int(cursor.get("rv", -1))
        sync.instance = cursor.get("instance")
    return {
        "nodes": len(doc.get("nodes", ())),
        "pending": len(doc.get("pending", ())),
        "bound": len(doc.get("bound", ())),
        "gangs": len(doc.get("gangs", ())),
        "quotas": len(doc.get("quotas", ()) or ()),
        "cursor_rv": int((doc.get("cursor") or {}).get("rv", -1)),
        "reservations_dropped": int(doc.get("reservations_dropped", 0)),
    }


def save(path: str, scheduler, sync=None) -> dict:
    """Capture + atomically persist (tmp file, ``os.replace``) so a
    crash mid-write leaves the previous checkpoint intact."""
    from koordinator_tpu.transport import wire

    doc, arrays = capture(scheduler, sync=sync)
    payload = wire.encode_payload(doc, arrays)
    tmp = f"{path}.tmp"
    with open(tmp, "wb") as f:
        f.write(payload)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
    return {"bytes": len(payload), "nodes": len(doc["nodes"]),
            "pending": len(doc["pending"]), "bound": len(doc["bound"])}


def load(path: str) -> tuple[dict, dict[str, np.ndarray]]:
    from koordinator_tpu.transport import wire

    with open(path, "rb") as f:
        return wire.decode_payload(f.read())


class CheckpointWriter:
    """Periodic warm-restart checkpointing (the scheduler binary's
    ``--checkpoint-path`` / ``--checkpoint-interval-seconds``).

    Owns one daemon thread; ``stop()`` writes a final cut so a PLANNED
    restart resumes from the freshest state, not the last interval.
    Lock discipline: the writer itself never holds ``scheduler.lock`` —
    each :func:`save` acquires it only for the capture walk, so rounds
    are blocked for the copy, never for serialization or disk I/O."""

    def __init__(self, path: str, scheduler, sync=None,
                 interval_s: float = 30.0):
        import threading

        self.path = path
        self.scheduler = scheduler
        self.sync = sync
        self.interval_s = float(interval_s)
        self.saves = 0
        self.errors = 0
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._run, name="ckpt-writer", daemon=True)

    def start(self) -> "CheckpointWriter":
        self._thread.start()
        return self

    def save_now(self) -> dict | None:
        try:
            stats = save(self.path, self.scheduler, self.sync)
            self.saves += 1
            return stats
        except Exception:
            # checkpointing is an optimization: a failed save must never
            # take the scheduler down (the fallback is the full
            # re-bootstrap warm restart replaces)
            self.errors += 1
            return None

    def _run(self) -> None:
        while not self._stop.wait(self.interval_s):
            self.save_now()

    def stop(self) -> None:
        self._stop.set()
        if self._thread.is_alive():
            self._thread.join(timeout=5.0)
        self.save_now()


def restore(path: str, scheduler, sync=None) -> dict:
    """load + restore_into, observing
    ``checkpoint_restore_duration_seconds``."""
    from koordinator_tpu import metrics

    start = time.monotonic()
    doc, arrays = load(path)
    stats = restore_into(scheduler, doc, arrays, sync=sync)
    stats["duration_s"] = time.monotonic() - start
    metrics.checkpoint_restore_duration_seconds.observe(
        stats["duration_s"])
    return stats
