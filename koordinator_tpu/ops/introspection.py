"""JAX solver introspection: recompiles, device bytes, profiler capture.

Three answers a self-observing scheduler needs when the latency SLO
burns (slo_monitor.py) — is it recompiles, device memory pressure, or
something else:

- :func:`instrument` wraps a jitted entry point and counts jit-cache
  misses (a trace+compile happened) per shape bucket into
  ``solver_recompiles_total{fn, shape}`` plus a live
  ``solver_jit_cache_size{fn}`` gauge; the wall of each such call is a
  ``kit.load:<fn>`` timeline span and sums into
  ``solver_load_seconds_total{fn}``.  The power-of-two bucketing in
  state/cluster_state bounds compiles to O(log N) over cluster life; a
  nonzero steady-state recompile RATE is exactly the regression the
  incremental-solve design must catch, not assume away.
- :func:`device_bytes` sums the device-resident footprint of any pytree
  (``ClusterState``, ``CandidateCache``) from array metadata — no
  transfer, no sync.
- :class:`ProfilerCapture` exposes ``jax.profiler`` start/stop as an
  on-demand, **gated-off-by-default** capture for the
  ``/debug/profile?seconds=N`` endpoint (a production scheduler must
  not let any caller start a device trace unless the operator enabled
  the gate at assembly).
"""

from __future__ import annotations

import math
import re
import tempfile
import threading
import time

from koordinator_tpu import metrics, timeline


def default_shape_of(args, kwargs) -> str:
    """Fallback shape-bucket label: the distinct leaf shapes of the
    positional args, largest first, capped for label sanity."""
    import jax

    shapes = set()
    for leaf in jax.tree.leaves(args):
        shape = getattr(leaf, "shape", None)
        if shape is not None:
            shapes.add(tuple(int(d) for d in shape))
    top = sorted(shapes,
                 key=lambda s: (-int(math.prod(s or (1,))), s))[:3]
    return "/".join("x".join(map(str, s)) if s else "scalar" for s in top)


class InstrumentedJit:
    """Callable wrapper over a jitted function that observes its jit
    cache: a call that grows the cache was a miss (trace+compile), and
    the miss is attributed to the caller-derived shape bucket.

    The wrapper is pass-through — donation, static args, and outputs
    behave exactly as on the wrapped function.  A function without the
    jit cache-size probe is refused: a wrapper that counted nothing
    would report "no recompiles" for a solver nobody is watching.
    """

    def __init__(self, fn, name: str, shape_of=None):
        probe = getattr(fn, "_cache_size", None)
        if probe is None:
            raise TypeError(
                f"instrument({name!r}): {fn!r} exposes no _cache_size "
                f"probe (not a jax.jit function?); its recompiles "
                f"cannot be counted")
        self.fn = fn
        self.name = name
        self.shape_of = shape_of or default_shape_of
        self._cache_size = probe
        self.misses = 0

    def __call__(self, *args, **kwargs):
        before = self._cache_size()
        t0 = time.perf_counter()
        out = self.fn(*args, **kwargs)
        after = self._cache_size()
        if after > before:
            t1 = time.perf_counter()
            timeline.RECORDER.add(t0, t1, "host_other",
                                  f"kit.load:{self.name}", n=after - before)
            metrics.solver_load_seconds.inc(t1 - t0,
                                            labels={"fn": self.name})
            try:
                shape = self.shape_of(args, kwargs)
            except Exception:  # noqa: BLE001 — labeling must not fail a solve
                shape = "unknown"
            self.misses += after - before
            metrics.solver_recompiles.inc(
                after - before, labels={"fn": self.name, "shape": shape})
            metrics.solver_jit_cache_size.set(
                float(after), labels={"fn": self.name})
        return out


def instrument(fn, name: str, shape_of=None) -> InstrumentedJit:
    """Wrap a jitted entry point for recompile accounting.

    ``shape_of(args, kwargs) -> str`` names the shape bucket; callers
    with a known signature should pass one (e.g. ``P{batch}xN{nodes}``)
    — the default derives a generic label from leaf shapes."""
    return InstrumentedJit(fn, name, shape_of=shape_of)


def device_bytes(tree) -> int:
    """Total ``nbytes`` of the array leaves of a pytree (0 for None).
    Metadata-only: never blocks on or transfers device buffers."""
    if tree is None:
        return 0
    import jax

    total = 0
    for leaf in jax.tree.leaves(tree):
        nbytes = getattr(leaf, "nbytes", None)
        if nbytes is not None:
            total += int(nbytes)
    return total


def device_bytes_by_shard(tree) -> dict[int, int]:
    """Per-device footprint of a pytree's arrays: {device_id: bytes}.

    Sums each leaf's addressable shards by the device they live on —
    node-axis-sharded solver tensors report one slice per device, while
    replicated leaves honestly charge EVERY device a full copy (that is
    what replication costs in HBM).  Metadata-only like
    :func:`device_bytes`; single-device arrays land on their device's id.
    """
    if tree is None:
        return {}
    import jax

    out: dict[int, int] = {}
    for leaf in jax.tree.leaves(tree):
        shards = getattr(leaf, "addressable_shards", None)
        if shards:
            for sh in shards:
                nbytes = getattr(sh.data, "nbytes", None)
                if nbytes is not None:
                    did = int(sh.device.id)
                    out[did] = out.get(did, 0) + int(nbytes)
        else:
            nbytes = getattr(leaf, "nbytes", None)
            if nbytes is not None:
                out[0] = out.get(0, 0) + int(nbytes)
    return out


def device_bytes_by_mesh_shard(tree, mesh) -> dict[tuple[int, int], int]:
    """Per-(pod_shard, node_shard) footprint of a pytree's arrays over a
    2-D solver mesh: {(pi, ni): bytes}.

    The same metadata-only walk as :func:`device_bytes_by_shard`, with
    device ids mapped to their mesh coordinates so a lopsided tile —
    the placement bug class of the 2-D layout — reads directly off the
    (pods, nodes) grid instead of a flat device list.  Devices outside
    the mesh (host-resident spill) land under ``(-1, -1)``."""
    if tree is None or mesh is None:
        return {}
    from koordinator_tpu.parallel.mesh import NODES_AXIS, PODS_AXIS

    import numpy as np

    coord_of: dict[int, tuple[int, int]] = {}
    grid = np.asarray(mesh.devices)
    axes = list(mesh.axis_names)
    pi_ax, ni_ax = axes.index(PODS_AXIS), axes.index(NODES_AXIS)
    for idx, dev in np.ndenumerate(grid):
        coord_of[int(dev.id)] = (int(idx[pi_ax]), int(idx[ni_ax]))
    out: dict[tuple[int, int], int] = {}
    for did, nbytes in device_bytes_by_shard(tree).items():
        key = coord_of.get(int(did), (-1, -1))
        out[key] = out.get(key, 0) + int(nbytes)
    return out


#: HLO collective op mnemonics counted by :func:`collective_counts`
_COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter",
                   "collective-permute", "all-to-all")


def collective_counts(compiled_text: str) -> dict[str, int]:
    """Count collective ops in compiled HLO text — the communication
    profile of a sharded solve (``jit(fn).lower(*args).compile()
    .as_text()``).  Returns {op: count} for the ops that appear."""
    out: dict[str, int] = {}
    for line in compiled_text.splitlines():
        stripped = line.lstrip()
        # HLO spells an op as "%name = type op-name(...)" (with -start/
        # -done pairs for async forms; count the starts only)
        for op in _COLLECTIVE_OPS:
            if (f" {op}(" in stripped or f" {op}-start(" in stripped
                    or stripped.startswith((f"{op}(", f"{op}-start("))):
                out[op] = out.get(op, 0) + 1
    return out


def compiled_collectives(jitted, *args, **kwargs) -> dict[str, int]:
    """Lower+compile a jitted callable against example args and report
    its collective-op counts (one AOT compile; the result is cached by
    the jit, so a subsequent real call does not recompile)."""
    compiled = jitted.lower(*args, **kwargs).compile()
    return collective_counts(compiled.as_text())


_REPLICA_GROUP_RE = re.compile(r"replica_groups=\{\{([0-9, ]+)\}")


def collective_axis_counts(compiled_text: str, mesh) -> dict[str, dict]:
    """Collective-op counts PER MESH AXIS: {axis: {op: count}}.

    Classifies each collective in the compiled HLO by its first replica
    group's size against the 2-D mesh's axis sizes — a nodes-axis psum
    groups ``dn`` devices, a pods-axis gather ``dp``, a whole-mesh
    reduction ``dp*dn`` (reported as ``"global"``).  Sizes matching
    neither (or an op with no parsable groups) land under ``"other"``;
    when the two axes are the same size the split is ambiguous and both
    axes' ops land under ``"pods_or_nodes"``.  A text-level heuristic —
    the only stable surface without a compiler API — good enough to put
    the communication profile of a sharded solve next to its wall time
    in the bench record."""
    if mesh is None:
        return {}
    from koordinator_tpu.parallel.mesh import (
        nodes_shard_count,
        pods_shard_count,
    )

    dp, dn = pods_shard_count(mesh), nodes_shard_count(mesh)
    by_size = {dp * dn: "global"}
    if dp == dn:
        by_size[dn] = "pods_or_nodes"
    else:
        by_size.update({dn: "nodes", dp: "pods"})
    out: dict[str, dict] = {}
    for line in compiled_text.splitlines():
        stripped = line.lstrip()
        for op in _COLLECTIVE_OPS:
            if not (f" {op}(" in stripped or f" {op}-start(" in stripped
                    or stripped.startswith((f"{op}(", f"{op}-start("))):
                continue
            m = _REPLICA_GROUP_RE.search(stripped)
            axis = "other"
            if m is not None:
                size = len([t for t in m.group(1).split(",") if t.strip()])
                axis = by_size.get(size, "other")
            out.setdefault(axis, {})
            out[axis][op] = out[axis].get(op, 0) + 1
    return out


class ProfileDisabled(Exception):
    """The profiling endpoint gate is off (the default)."""


class ProfileBusy(Exception):
    """A capture is already in flight (jax allows one trace at a time)."""


class ProfilerCapture:
    """On-demand ``jax.profiler`` trace capture behind an explicit gate.

    ``enabled=False`` (the default) refuses every capture with
    :class:`ProfileDisabled` — the endpoint ships dark and an operator
    turns it on at assembly (``--enable-profile-endpoint``).  Captures
    serialize on a lock and are clamped to ``max_seconds``.
    ``profiler``/``sleep`` are injectable for tests.
    """

    def __init__(self, enabled: bool = False, out_dir: str | None = None,
                 max_seconds: float = 30.0, profiler=None, sleep=time.sleep):
        self.enabled = enabled
        self.out_dir = out_dir
        self.max_seconds = max_seconds
        self._profiler = profiler
        self._sleep = sleep
        self._lock = threading.Lock()
        self.captures = 0

    def _jax_profiler(self):
        if self._profiler is not None:
            return self._profiler
        import jax.profiler

        return jax.profiler

    def capture(self, seconds: float) -> dict:
        """Run one trace for ``seconds`` (clamped to (0, max_seconds]);
        returns ``{"dir", "seconds"}`` where ``dir`` holds the
        TensorBoard-loadable trace."""
        if not self.enabled:
            raise ProfileDisabled(
                "profiling endpoint disabled (enable at assembly with "
                "--enable-profile-endpoint)")
        seconds = float(seconds)
        if not math.isfinite(seconds):
            # nan survives min/max clamping and would start a trace
            # only to die inside sleep()
            raise ValueError("seconds must be finite")
        seconds = min(max(seconds, 0.001), self.max_seconds)
        if not self._lock.acquire(blocking=False):
            raise ProfileBusy("a profiler capture is already running")
        try:
            out_dir = self.out_dir or tempfile.mkdtemp(
                prefix="koord-jax-profile-")
            profiler = self._jax_profiler()
            profiler.start_trace(out_dir)
            try:
                self._sleep(seconds)
            finally:
                profiler.stop_trace()
            self.captures += 1
            return {"dir": out_dir, "seconds": seconds}
        finally:
            self._lock.release()
