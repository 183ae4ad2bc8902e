"""Randomized invariants of the cpuset take (``grant_cpu_rows`` on one
node row, the solve's stage and the host take alike).

test_numa.py pins the reference scenarios (cpu_accumulator.go policies)
at hand-built topologies; this sweeps random topologies, ref-counts,
and bans (CPUs taken out of the row, as NUMA-level exclusivity does on
the host) across every bind policy:

  (count)    ok => exactly n_cpus selected (FullPCPUs: rounded up to
             whole cores); !ok => nothing selected
  (legal)    selected CPUs are valid, under max_ref, and never banned
  (cores)    FullPCPUs selects only whole, fully-free physical cores
  (honest)   !ok only when the policy really cannot be satisfied —
             checked against an independent count of eligible CPUs

Then the kernels' lookups along the CPU axis against their gather forms
(``tests/numa_reference.py``), bit for bit, on random planes of 1-4
threads a core with strided siblings, padded rows, offline CPUs,
exclusive holders and SingleNUMANode; and the compiled kernels hold no
gather at all.
"""

import functools

import numpy as np
import pytest

from tests import numa_reference
from tests.conftest import prop_seeds

from koordinator_tpu.ops import numa
from koordinator_tpu.ops.numa import (
    BIND_DEFAULT,
    BIND_FULL_PCPUS,
    BIND_SPREAD_BY_PCPUS,
    CPUTopology,
    PodCPURequests,
    grant_cpu_rows,
)
from koordinator_tpu.scheduler.cpu_manager import NodeCPUState


def _random_topo(rng: np.random.Generator):
    sockets = int(rng.integers(1, 3))
    numa_per = int(rng.integers(1, 3))
    cores_per = int(rng.integers(2, 5))
    threads = 2
    n = sockets * numa_per * cores_per * threads
    core_of = np.repeat(np.arange(sockets * numa_per * cores_per), threads)
    numa_of = core_of // cores_per
    socket_of = numa_of // numa_per
    return CPUTopology.build(core_of.astype(np.int32),
                             numa_of.astype(np.int32),
                             socket_of.astype(np.int32)), n


@pytest.mark.parametrize("seed", prop_seeds(20))
@pytest.mark.parametrize("bind", [BIND_DEFAULT, BIND_FULL_PCPUS,
                                  BIND_SPREAD_BY_PCPUS])
def test_take_invariants(seed, bind):
    rng = np.random.default_rng(seed)
    topo, n = _random_topo(rng)
    cap = topo.capacity
    max_ref = int(rng.integers(1, 3))
    ref = np.zeros(cap, np.int32)
    ref[:n] = rng.integers(0, max_ref + 1, n)
    banned = np.zeros(cap, bool)
    banned[:n] = rng.random(n) < 0.2
    want = int(rng.integers(1, n + 2))

    row = NodeCPUState(topology=topo, ref_count=ref,
                       max_ref=max_ref).plane_row()
    row = row.replace(valid=row.valid & ~banned[None])
    sel, ok = grant_cpu_rows(row, row.held, PodCPURequests(
        n=np.array([want], np.int32),
        full=np.array([bind == BIND_FULL_PCPUS]),
        spread=np.array([bind == BIND_SPREAD_BY_PCPUS]),
        excl=np.array([False]), wants=np.array([True])))
    sel, ok = np.asarray(sel)[0], bool(ok[0])
    valid = np.asarray(topo.valid)
    core_of = np.asarray(topo.core_of)

    free = valid & (ref < max_ref) & ~banned
    if bind == BIND_FULL_PCPUS:
        # whole cores only: a core is takeable iff every sibling is free
        core_free_count = np.bincount(core_of[free],
                                      minlength=core_of.max() + 1)
        core_size = np.bincount(core_of[valid],
                                minlength=core_of.max() + 1)
        takeable = np.isin(core_of, np.flatnonzero(
            (core_size > 0) & (core_free_count == core_size))) & free
        threads = int(core_size[core_size > 0].max())
        need = -(-want // threads) * threads   # rounded to whole cores
        can = takeable.sum() >= need
    else:
        takeable = free
        need = want
        can = free.sum() >= want

    if ok:
        # (count)
        assert sel.sum() == need, (seed, bind, sel.sum(), need)
        # (legal)
        assert not (sel & ~free).any(), f"seed {seed}: illegal cpu taken"
        if bind == BIND_FULL_PCPUS:
            # (cores) selected cores are complete
            sel_cores = np.bincount(core_of[sel],
                                    minlength=core_of.max() + 1)
            partial = (sel_cores > 0) & (sel_cores != core_size)
            assert not partial.any(), f"seed {seed}: partial core taken"
            assert not (sel & ~takeable).any()
    else:
        assert sel.sum() == 0, f"seed {seed}: !ok but cpus selected"
        # (honest) failure only when genuinely unsatisfiable
        assert not can, (
            f"seed {seed} bind={bind}: refused a satisfiable request "
            f"(want {want}, takeable {int(takeable.sum())})")


# -- compare-and-reduce against the gather forms ------------------------------

ROWS, CAP = 16, 32


def random_plane(rng: np.random.Generator, threads: int):
    """A (ROWS, CAP, threads) ``CPUState`` of random nodes, each laid out
    by ``NodeCPUState.plane_row``: CPU c on core c mod cores (siblings c,
    c + cores, ...), up to ``threads`` a core, a tenth of the CPUs offline
    (cores of fewer threads, fewer valid CPUs than CAP, -1 padded rows),
    1-4 NUMA nodes, refs up to max_ref and exclusive holders in ``held``,
    SingleNUMANode on about half the rows."""
    import jax.numpy as jnp

    fields = {k: [] for k in ("held", "core", "sib", "numa", "valid",
                              "max_ref", "tpc", "single_numa")}
    for _ in range(ROWS):
        t = int(rng.integers(1, threads + 1))
        cores = int(rng.integers(1, CAP // t + 1))
        cpu = np.arange(cores * t)
        core_of = cpu % cores
        numa_of = core_of * int(rng.integers(1, min(4, cores) + 1)) // cores
        online = rng.random(len(cpu)) >= 0.1
        online[0] = True
        topo = CPUTopology.build(core_of[online], numa_of[online],
                                 np.zeros(online.sum(), np.int32),
                                 capacity=CAP)
        max_ref = int(rng.integers(1, 4))
        row = NodeCPUState(topology=topo,
                           ref_count=np.zeros(CAP, np.int32),
                           max_ref=max_ref).plane_row()
        valid = np.asarray(row.valid[0])
        held = np.where(valid & (rng.random(CAP) < 0.4),
                        rng.integers(1, max_ref + 1, CAP), 0)
        held += numa.EXCL_UNIT * (valid & (rng.random(CAP) < 0.1))
        sib = np.full((CAP, threads), -1, np.int32)
        sib[:, :row.sib.shape[-1]] = row.sib[0]
        for name, value in (("held", held), ("core", row.core[0]),
                            ("sib", sib), ("numa", row.numa[0]),
                            ("valid", valid), ("max_ref", max_ref),
                            ("tpc", row.tpc[0]),
                            ("single_numa", rng.random() < 0.5)):
            fields[name].append(value)
    arrays = {k: jnp.asarray(np.stack(v) if np.ndim(v[0]) else np.array(v))
              for k, v in fields.items()}
    arrays["held"] = arrays["held"].astype(jnp.int32)
    return numa.CPUState(**arrays)


def random_asks(rng: np.random.Generator) -> PodCPURequests:
    """One request a row: 1 to 8 CPUs, or now and then more than a node
    has; the default, FullPCPUs or SpreadByPCPUs bind, exclusive or not;
    a few rows not pinned."""
    bind = rng.integers(0, 3, ROWS)
    n = np.where(rng.random(ROWS) < 0.9, rng.integers(1, 9, ROWS), CAP + 2)
    return PodCPURequests(
        n=n.astype(np.int32),
        full=bind == BIND_FULL_PCPUS, spread=bind == BIND_SPREAD_BY_PCPUS,
        excl=rng.random(ROWS) < 0.4, wants=rng.random(ROWS) < 0.9)


@functools.cache
def kernels():
    """(program, reference) jitted: the core sums of three per-CPU
    values, the eligibility masks, the pools and the take."""
    import jax

    def program(cpus, x, req):
        on = numa._on_core(cpus.sib)
        return ([numa._core_sum(v, on) for v in x],
                numa._eligible(cpus, cpus.held),
                numa.node_pools(cpus, cpus.held),
                numa.grant_cpu_rows(cpus, cpus.held, req))

    def reference(cpus, x, req):
        ref = numa_reference
        return ([ref._core_sum(v, cpus.sib) for v in x],
                ref._eligible(cpus, cpus.held),
                ref.node_pools(cpus, cpus.held),
                ref.grant_cpu_rows(cpus, cpus.held, req))

    return jax.jit(program), jax.jit(reference)


@pytest.mark.parametrize("seed", prop_seeds(3))
@pytest.mark.parametrize("threads", [1, 2, 3, 4])
def test_compare_and_reduce_equals_the_gather_forms(seed, threads):
    """Core sums, eligibility masks, pools and the take's selection and
    ok of the program equal the gather forms' bit for bit."""
    import jax

    rng = np.random.default_rng(1000 * threads + seed)
    cpus = random_plane(rng, threads)
    x = [cpus.held, cpus.held // numa.EXCL_UNIT,
         np.asarray(rng.integers(-5, 6, (ROWS, CAP)), np.int32)]
    req = random_asks(rng)
    program, reference = kernels()
    got, want = program(cpus, x, req), reference(cpus, x, req)
    leaves = jax.tree.leaves(got)
    assert len(leaves) == len(jax.tree.leaves(want)) == 10
    for i, (g, w) in enumerate(zip(leaves, jax.tree.leaves(want))):
        assert g.dtype == w.dtype, i
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w), str(i))
    sel, ok = map(np.asarray, got[-1])
    assert ok.any() and sel.any() and not ok.all(), (seed, threads)


def test_the_compiled_kernels_hold_no_gather():
    """``node_pools`` and ``grant_cpu_rows``, compiled at a small shape,
    read along the CPU axis by compares alone; their gather forms do
    not, which is what the guard would see if the form came back."""
    import jax

    rng = np.random.default_rng(7)
    cpus, req = random_plane(rng, 2), random_asks(rng)

    def gathers(fn, *args):
        return jax.jit(fn).lower(*args).compile().as_text().count("gather(")

    assert gathers(numa.node_pools, cpus, cpus.held) == 0
    assert gathers(numa.grant_cpu_rows, cpus, cpus.held, req) == 0
    assert gathers(numa_reference.node_pools, cpus, cpus.held) > 0
    assert gathers(numa_reference.grant_cpu_rows, cpus, cpus.held, req) > 0
