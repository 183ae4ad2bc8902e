"""Test-only reference for the cpuset kernels' lookups along a node's CPU
axis: ``ops/numa``'s ``_core_sum``, ``_eligible`` and ``grant_cpu_rows``
as they were while they read a core's siblings, a CPU's NUMA pool and the
n-th key by per-element gathers (``take_along_axis`` over the CPU axis).
The program reads the same by compare-and-reduce; the two must agree bit
for bit, on one row and through every solve that grants cpusets.
``patch`` puts these forms in the program's place for a solve traced
after it."""

import jax
import jax.numpy as jnp

from koordinator_tpu.ops import numa


def _core_sum(x, sib):
    """(X, C): ``x`` summed over each CPU's core, by the sibling table."""
    x_, c, t = sib.shape
    got = jnp.take_along_axis(
        x, jnp.maximum(sib, 0).reshape(x_, c * t), axis=1).reshape(x_, c, t)
    return jnp.sum(jnp.where(sib >= 0, got, 0), axis=-1)


def _eligible(cpus, held):
    """The three eligibility masks over rows of the plane ((X, C) each)."""
    ref = held % numa.EXCL_UNIT
    core_ref = _core_sum(ref, cpus.sib)
    core_excl = _core_sum(held // numa.EXCL_UNIT, cpus.sib)
    base = cpus.valid & (ref < cpus.max_ref[:, None]) & (core_excl == 0)
    core_size = jnp.sum(cpus.sib >= 0, axis=-1)
    whole = base & (_core_sum(base.astype(jnp.int32), cpus.sib) == core_size)
    return base, whole, base & (core_ref == 0)


def grant_cpu_rows(rows, held, req):
    """Reserve for P pods, each on a node row of its own: ((P, C) bool
    selection, (P,) ok), the take's key sorted and its first n taken."""
    p, c = rows.valid.shape
    base, whole, free_x = _eligible(rows, held)
    kind = req.kind[:, None]
    eligible = jnp.where(kind == 2, free_x, jnp.where(kind == 1, whole, base))
    n = numa._n_eff(req.n, req.full, rows.tpc)
    pool = jnp.take_along_axis(numa._numa_counts(eligible, rows.numa),
                               rows.numa, axis=1)
    sat = (pool >= n[:, None]) & eligible
    eligible = jnp.where(rows.single_numa[:, None], sat, eligible)
    cpu = jnp.arange(c, dtype=jnp.int32)[None, :]
    x_, _, t = rows.sib.shape
    sib = rows.sib
    sib_ok = jnp.take_along_axis(
        eligible, jnp.maximum(sib, 0).reshape(x_, c * t),
        axis=1).reshape(x_, c, t)
    rank = jnp.sum((sib >= 0) & (sib < cpu[:, :, None]) & sib_ok, axis=-1)
    intra = jnp.where(req.spread[:, None],
                      (rank * c + rows.core) * c + cpu,
                      rows.core * c + cpu)
    key = ((~eligible).astype(jnp.int32) << 28
           | (~sat).astype(jnp.int32) << 27
           | jnp.minimum(pool, 255) << 19
           | rows.numa << 16
           | intra)
    ranked = jnp.sort(key, axis=-1)
    nth = jnp.take_along_axis(ranked, jnp.clip(n - 1, 0, c - 1)[:, None],
                              axis=1)
    ok = req.wants & (n >= 1) & (jnp.sum(eligible, axis=-1) >= n)
    return (key <= nth) & eligible & ok[:, None], ok


def node_pools(cpus, held):
    """``numa.node_pools`` over this module's masks."""
    masks = jnp.stack(_eligible(cpus, held))
    per_numa = jax.vmap(numa._numa_counts, in_axes=(0, None))(masks, cpus.numa)
    return jnp.sum(per_numa, axis=-1), jnp.max(per_numa, axis=-1)


def patch(monkeypatch, traced: list | None = None) -> None:
    """Put the gather forms in the program's place: ``numa.node_pools``
    reads ``numa._eligible``, and the solves call ``numa.grant_cpu_rows``.
    Each call while tracing appends its name to ``traced``."""

    def counted(name, fn):
        def run(*args):
            if traced is not None:
                traced.append(name)
            return fn(*args)
        return run

    monkeypatch.setattr(numa, "_eligible", counted("_eligible", _eligible))
    monkeypatch.setattr(numa, "grant_cpu_rows",
                        counted("grant_cpu_rows", grant_cpu_rows))
