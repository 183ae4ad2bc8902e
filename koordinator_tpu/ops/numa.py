"""Fine-grained CPU orchestration: the cpuset Filter and take + NUMA hints.

TPU-native equivalent of the reference's nodenumaresource plugin
(pkg/scheduler/plugins/nodenumaresource/: cpu_accumulator.go takeCPUs,
topology hint generation in topology_hint.go) and the scheduler-side
topology manager (pkg/scheduler/frameworkext/topologymanager/: policies
none/best-effort/restricted/single-numa-node).

Design split (mirrors how the reference actually uses this logic):

- **Filter is batched, count-based.** Whether a pod's cpuset request fits a
  node needs only per-NUMA free counts of the right eligibility kind
  (:func:`node_pools`), over every node of the plane at once
  (:func:`cpuset_fit_pods`, :func:`candidate_cpuset_fit`).
- **Reserve is sort-based, one node row a pod.** The cpuset selection
  (take-by-topology, :func:`grant_cpu_rows`) builds one key per logical CPU
  from (eligibility, NUMA-satisfies-alone, NUMA pool, NUMA id, core or
  sibling rank, CPU id), sorts, takes the first n. This replaces the
  accumulator's nested free-cores-in-node/socket walks
  (cpu_accumulator.go:108-200) with one vectorized sort.  The solve's
  stage and the host take of the paths without it
  (``scheduler/cpu_manager.CPUManager.allocate``) call the same function.

Bind policies (apis/extension/numa_aware.go:101-107): FullPCPUs allocates
whole physical cores; SpreadByPCPUs allocates one sibling per core first.
The take's NUMA strategy is MostAllocated (the fullest NUMA node first);
LeastAllocated remains a scoring strategy (:func:`numa_score`) only.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

#: Hint enumeration bound: masks are enumerated statically as 2^MAX_NUMA
#: combinations (the reference's bitmask.IterateBitMasks over NUMA nodes).
MAX_NUMA = 8

# CPUBindPolicy (numa_aware.go:101-107)
BIND_DEFAULT = 0
BIND_FULL_PCPUS = 1
BIND_SPREAD_BY_PCPUS = 2

# NUMAAllocateStrategy
STRATEGY_MOST_ALLOCATED = 0   # pack: prefer NUMA nodes with least free
STRATEGY_LEAST_ALLOCATED = 1  # spread: prefer NUMA nodes with most free

# CPUExclusivePolicy (numa_aware.go:114-118)
EXCLUSIVE_NONE = 0
EXCLUSIVE_PCPU_LEVEL = 1      # no other pod may share my physical cores
EXCLUSIVE_NUMA_LEVEL = 2      # no other pod may share my NUMA nodes

# Topology manager policies (frameworkext/topologymanager/policy_*.go)
POLICY_NONE = 0
POLICY_BEST_EFFORT = 1
POLICY_RESTRICTED = 2
POLICY_SINGLE_NUMA_NODE = 3


@struct.dataclass
class CPUTopology:
    """Per-logical-CPU topology arrays, padded to a static CPU capacity C."""

    core_of: jax.Array     # (C,) int32 — physical core id (< C)
    numa_of: jax.Array     # (C,) int32 — NUMA node id (< MAX_NUMA)
    socket_of: jax.Array   # (C,) int32
    valid: jax.Array       # (C,) bool

    @property
    def capacity(self) -> int:
        return self.valid.shape[0]

    @classmethod
    def build(
        cls,
        core_of: np.ndarray,
        numa_of: np.ndarray,
        socket_of: np.ndarray,
        capacity: int | None = None,
    ) -> "CPUTopology":
        # at call time: the state module imports this one
        from koordinator_tpu.state.cluster_state import _bucket

        n = len(core_of)
        cap = capacity or _bucket(max(n, 1), minimum=8)

        def pad(a):
            out = np.zeros(cap, np.int32)
            out[:n] = a
            return jnp.asarray(out)

        valid = np.zeros(cap, bool)
        valid[:n] = True
        return cls(pad(core_of), pad(numa_of), pad(socket_of), jnp.asarray(valid))

    @classmethod
    def uniform(
        cls,
        sockets: int = 1,
        numa_per_socket: int = 1,
        cores_per_numa: int = 4,
        threads_per_core: int = 2,
        capacity: int | None = None,
    ) -> "CPUTopology":
        """Synthetic SMT topology (lscpu-shaped, util/system/lscpu.go)."""
        n = sockets * numa_per_socket * cores_per_numa * threads_per_core
        cpu = np.arange(n)
        core = cpu // threads_per_core
        numa = core // cores_per_numa
        sock = numa // numa_per_socket
        return cls.build(core, numa, sock, capacity=capacity)


# -- NUMA topology hints + topology manager (frameworkext/topologymanager) ----


def _mask_table() -> jnp.ndarray:
    """(2^MAX_NUMA, MAX_NUMA) bool — every NUMA-node bitmask combination."""
    m = np.arange(1 << MAX_NUMA)
    return jnp.asarray((m[:, None] >> np.arange(MAX_NUMA)) & 1, bool)


_MASKS = _mask_table()
_POPCOUNT = jnp.sum(_MASKS.astype(jnp.int32), axis=-1)


def numa_hints(
    numa_free: jnp.ndarray,    # (MAX_NUMA,) free units per NUMA node
    request: jnp.ndarray,      # () requested units
) -> jnp.ndarray:
    """(2^MAX_NUMA,) bool feasibility per NUMA mask (hint generation).

    A mask is feasible if the free capacity across its member nodes covers
    the request (GenerateMachineInfoHints-style per-provider hints).
    """
    totals = _MASKS.astype(jnp.int32) @ numa_free.astype(jnp.int32)
    nonempty = _POPCOUNT > 0
    return (totals >= request) & nonempty


def preferred_mask(feasible: jnp.ndarray) -> jnp.ndarray:
    """() int32 — the feasible mask with fewest NUMA nodes (-1 if none).

    The topology manager's 'preferred' bit: minimal-width masks win
    (policy.go mergeProvidersHints narrowest-mask preference).
    """
    key = jnp.where(feasible, _POPCOUNT * (1 << MAX_NUMA) + jnp.arange(1 << MAX_NUMA),
                    jnp.iinfo(jnp.int32).max)
    best = jnp.argmin(key)
    return jnp.where(jnp.any(feasible), best, -1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("policy",))
def merge_hints(
    provider_feasible: jnp.ndarray,  # (K, 2^MAX_NUMA) bool — one row per provider
    policy: int = POLICY_BEST_EFFORT,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Topology manager Admit: merge provider hints under a policy.

    Returns (admit, mask): mask is the chosen NUMA bitmask index (-1 when the
    merge found none; best-effort still admits in that case, matching
    policy_best_effort.go; restricted and single-numa-node reject).
    """
    merged = jnp.all(provider_feasible, axis=0)
    if policy == POLICY_SINGLE_NUMA_NODE:
        merged = merged & (_POPCOUNT == 1)
    best = preferred_mask(merged)
    has = best >= 0
    if policy == POLICY_NONE:
        admit = jnp.bool_(True)
    elif policy == POLICY_BEST_EFFORT:
        admit = jnp.bool_(True)
    else:  # RESTRICTED / SINGLE_NUMA_NODE
        admit = has
    return admit, best


def numa_score(
    numa_free: jnp.ndarray,    # (MAX_NUMA,)
    numa_total: jnp.ndarray,   # (MAX_NUMA,)
    request: jnp.ndarray,      # ()
    strategy: int = STRATEGY_MOST_ALLOCATED,
) -> jnp.ndarray:
    """() int32 in [0, 100] — NUMA-affinity score for one node.

    Fitting inside a single NUMA node is worth half the range; the other half
    follows the allocate strategy applied to the best candidate node
    (score per resource_manager.go's most/least-allocated NUMA scoring).
    """
    fits_single = jnp.any(numa_free >= request)
    total = jnp.maximum(numa_total, 1)
    if strategy == STRATEGY_MOST_ALLOCATED:
        per_numa = jnp.where(
            numa_free >= request, 100 - (numa_free * 100) // total, 0
        )
    else:
        per_numa = jnp.where(numa_free >= request, (numa_free * 100) // total, 0)
    strat = jnp.max(per_numa)
    return (jnp.where(fits_single, 50, 0) + strat // 2).astype(jnp.int32)


# ---------------------------------------------------------------------------
# The cpuset stage of the batched solve
# ---------------------------------------------------------------------------
#
# NodeNUMAResource's Filter and Reserve for LSR / LSE pods, over every pod
# of a batch at once, in the node rows of ``ClusterState.cpus``.  A pod is
# pinned when its QoS is LSR or LSE and it asks for at least one whole CPU
# (``requests[CPU] // 1000``, the rule the commit always used); its
# resource-spec (``PodBatch.cpu_spec``: bind policy, exclusive policy) says
# how.  The take sorts one key per CPU of one node row, with two
# departures from upstream, both written in
# ``benchmarks/reference/cpuset.py``: the NUMA node's id breaks a tie
# between two NUMA nodes of equal pool (so a take that one NUMA node
# satisfies never spans two), and the strategy is MostAllocated alone.  A node whose ``numa-topology-policy`` is
# SingleNUMANode takes only inside one NUMA node; every other policy is
# taken as none.  Exclusivity: a CPU on a core an exclusive (PCPU-level)
# pod holds is nobody's, and an exclusive pod takes only CPUs of cores
# nobody holds; a NUMA-level pod is held to PCPU level here.  Every
# lookup along a node's CPU axis (a core's siblings, a CPU's NUMA pool,
# the n-th key) is a compare against the ids and a reduce, never a
# gather: a TPU gathers along the minor axis one element at a time, and
# C <= MAX_CPUS_PER_NODE caps the (X, C, C) compare.

#: a CPU's exclusive holders count in ``CPUState.held`` in units of this
EXCL_UNIT = 1 << 8
#: the grant key's field widths
MAX_CPUS_PER_NODE = 128
MAX_THREADS_PER_CORE = 4

#: ``PodBatch.cpu_spec`` packs (bind policy) | (exclusive policy << 2)
SPEC_EXCL_SHIFT = 2
#: a pinned pod without a resource-spec: the commit's old rule, the
#: default bind policy at PCPU-level exclusivity
DEFAULT_CPU_SPEC = BIND_DEFAULT | (EXCLUSIVE_PCPU_LEVEL << SPEC_EXCL_SHIFT)


def pack_cpu_spec(bind_policy: int, exclusive_policy: int) -> int:
    return int(bind_policy) | (int(exclusive_policy) << SPEC_EXCL_SHIFT)


@struct.dataclass
class CPUState:
    """Every node's logical CPUs, padded (N, C): the plane the solve's
    cpuset stage reads and takes from."""

    held: jax.Array         # (N, C) int32: refs + EXCL_UNIT * exclusive refs
    core: jax.Array         # (N, C) int32 dense node-local core id
    sib: jax.Array          # (N, C, T) int32 the CPUs of my core, -1 padded
    numa: jax.Array         # (N, C) int32 NUMA node (< MAX_NUMA)
    valid: jax.Array        # (N, C) bool
    max_ref: jax.Array      # (N,) int32
    tpc: jax.Array          # (N,) int32 threads of the node's largest core
    single_numa: jax.Array  # (N,) bool numa-topology-policy SingleNUMANode

    @property
    def shape(self) -> tuple[int, int]:
        return self.valid.shape


@struct.dataclass
class CPUGrants:
    """What the cpuset stage decided for each pod of a solve."""

    selection: jax.Array   # (P, C) bool — the CPUs granted on its node
    lost_races: jax.Array  # (P,) int32 — accepts undone for want of cores
    #: (P,) int32 the order the grants were taken in: on one node a grant
    #: of a smaller turn was taken first, and one of a larger turn against
    #: what it left (a granted row's alone means anything)
    turn: jax.Array


@struct.dataclass
class PodCPURequests:
    """A batch's cpuset requests (all (P,))."""

    n: jax.Array        # int32 logical CPUs asked (before whole-core rounding)
    full: jax.Array     # bool FullPCPUs: whole free cores, rounded up
    spread: jax.Array   # bool SpreadByPCPUs: one sibling per core first
    excl: jax.Array     # bool PCPU-level (or NUMA-level) exclusive
    wants: jax.Array    # bool the pod is pinned at all

    @property
    def kind(self) -> jax.Array:
        """Which eligibility mask the pod reads: 0 free, 1 whole free
        cores, 2 CPUs of cores nobody holds."""
        return jnp.where(self.excl, 2, jnp.where(self.full, 1, 0))

    @property
    def unit(self) -> jax.Array:
        """(P,) what a granted CPU adds to ``held``."""
        return 1 + EXCL_UNIT * self.excl.astype(jnp.int32)


def pod_cpu_requests(pods) -> PodCPURequests:
    """The cpuset request of every pod of a ``PodBatch``."""
    from koordinator_tpu.api.qos import QoSClass
    from koordinator_tpu.api.resources import ResourceDim

    n = pods.requests[:, ResourceDim.CPU] // 1000
    qos = pods.qos.astype(jnp.int32)
    spec = (jnp.full(pods.capacity, DEFAULT_CPU_SPEC, jnp.int32)
            if pods.cpu_spec is None else pods.cpu_spec.astype(jnp.int32))
    bind = spec & ((1 << SPEC_EXCL_SHIFT) - 1)
    return PodCPURequests(
        n=n.astype(jnp.int32),
        full=bind == BIND_FULL_PCPUS,
        spread=bind == BIND_SPREAD_BY_PCPUS,
        excl=(spec >> SPEC_EXCL_SHIFT) != EXCLUSIVE_NONE,
        wants=(pods.valid & (n >= 1)
               & ((qos == int(QoSClass.LSR)) | (qos == int(QoSClass.LSE)))))


def _on_core(sib):
    """(X, C, C) bool: ``on[x, d, c]``, CPU d is one of CPU c's siblings
    (the sibling table compared against the CPU ids)."""
    cpu = jnp.arange(sib.shape[1], dtype=sib.dtype)
    return jnp.any(sib[:, None, :, :] == cpu[None, :, None, None], axis=-1)


def _core_sum(x, on):
    """(X, C): ``x`` summed over each CPU's core (``on``: :func:`_on_core`)."""
    return jnp.sum(jnp.where(on, x[:, :, None], 0), axis=1)


def _eligible(cpus: CPUState, held):
    """The three eligibility masks of :attr:`PodCPURequests.kind` over
    rows of the plane ((X, C) each) against ``held``."""
    on = _on_core(cpus.sib)
    ref = held % EXCL_UNIT
    core_ref = _core_sum(ref, on)
    core_excl = _core_sum(held // EXCL_UNIT, on)
    base = cpus.valid & (ref < cpus.max_ref[:, None]) & (core_excl == 0)
    core_size = jnp.sum(cpus.sib >= 0, axis=-1)
    whole = base & (_core_sum(base.astype(jnp.int32), on) == core_size)
    return base, whole, base & (core_ref == 0)


def _numa_counts(mask, numa):
    """(X, MAX_NUMA) eligible CPUs per NUMA node."""
    onehot = numa[:, :, None] == jnp.arange(MAX_NUMA, dtype=numa.dtype)
    return jnp.sum(mask[:, :, None] & onehot, axis=1, dtype=jnp.int32)


def _n_eff(n, full, tpc):
    """CPUs a take needs: FullPCPUs rounds up to whole cores."""
    t = jnp.maximum(tpc, 1)
    return jnp.where(full, -(-n // t) * t, n)


def node_pools(cpus: CPUState, held):
    """((3, X) CPUs on the node, (3, X) CPUs in its best NUMA node) per
    eligibility kind: what the count Filter compares.  A node's pools
    read its own row alone, so a solve that takes from one node
    recomputes that node's column (the exact scan's step) instead of
    the plane's."""
    masks = jnp.stack(_eligible(cpus, held))               # (3, X, C)
    onehot = cpus.numa[:, :, None] == jnp.arange(MAX_NUMA,
                                                 dtype=cpus.numa.dtype)
    per_numa = jnp.sum(masks[..., None] & onehot[None], axis=2,
                       dtype=jnp.int32)                     # (3, X, M)
    return jnp.sum(per_numa, axis=-1), jnp.max(per_numa, axis=-1)


def _fit(tot, best, single, tpc, req: PodCPURequests):
    """(P, X) the count Filter from pools gathered per pod ((3, P or 1,
    X) each) against the node's policy and threads ((P or 1, X))."""
    kind = req.kind[:, None]
    pick = lambda a: jnp.where(kind == 2, a[2],
                               jnp.where(kind == 1, a[1], a[0]))
    pool = jnp.where(single, pick(best), pick(tot))
    need = _n_eff(req.n[:, None], req.full[:, None], tpc)
    return (pool >= need) | ~req.wants[:, None]


@jax.named_scope("cpuset/fit")
def cpuset_fit_pods(cpus: CPUState, pods, pools=None) -> jnp.ndarray:
    """(P, N) bool: NodeNUMAResource's Filter for every pod of a batch;
    a pod that is not pinned fits everywhere.  ``pools`` (of
    :func:`node_pools`) stand in for those of ``cpus.held`` where a
    solve carries its own."""
    req = pod_cpu_requests(pods)
    tot, best = node_pools(cpus, cpus.held) if pools is None else pools
    return _fit(tot[:, None, :], best[:, None, :], cpus.single_numa[None, :],
                cpus.tpc[None, :], req)


@jax.named_scope("cpuset/fit")
def candidate_cpuset_fit(cpus: CPUState, held, req: PodCPURequests,
                         cand_node: jnp.ndarray) -> jnp.ndarray:
    """(P, k) bool: the same Filter at each pod's candidate nodes only,
    against the ``held`` a solve's rounds carry."""
    tot, best = node_pools(cpus, held)
    return _fit(tot[:, cand_node], best[:, cand_node],
                cpus.single_numa[cand_node], cpus.tpc[cand_node], req)


@jax.named_scope("cpuset/grant")
def grant_cpu_rows(rows: CPUState, held, req: PodCPURequests):
    """Reserve for P pods, each on a node row of its own (``rows``: the
    plane gathered per pod, (P, C) ``held``): ((P, C) bool selection,
    (P,) ok).  The take sorts one key per CPU: eligible first, then
    CPUs whose NUMA node alone satisfies the take, then MostAllocated
    (the NUMA node with the fewest eligible CPUs first), then the NUMA
    node's id, then whole cores together (FullPCPUs, default) or one
    sibling per core round-robin (SpreadByPCPUs), then the CPU id; the
    first n are taken.  On a SingleNUMANode node only the CPUs of NUMA
    nodes that satisfy the take alone are eligible."""
    p, c = rows.valid.shape
    if c > MAX_CPUS_PER_NODE or rows.sib.shape[-1] > MAX_THREADS_PER_CORE:
        raise ValueError(f"{c} CPUs a node, {rows.sib.shape[-1]} a core "
                         f"exceed the grant key's fields")
    base, whole, free_x = _eligible(rows, held)
    kind = req.kind[:, None]
    eligible = jnp.where(kind == 2, free_x, jnp.where(kind == 1, whole, base))
    n = _n_eff(req.n, req.full, rows.tpc)                      # (P,)
    # the eligible CPUs of each CPU's NUMA node, selected by its id
    at_numa = rows.numa[:, None, :] == jnp.arange(
        MAX_NUMA, dtype=rows.numa.dtype)[None, :, None]         # (P, M, C)
    pool = jnp.sum(jnp.where(
        at_numa, _numa_counts(eligible, rows.numa)[:, :, None], 0),
        axis=1)                                                 # (P, C)
    sat = (pool >= n[:, None]) & eligible
    eligible = jnp.where(rows.single_numa[:, None], sat, eligible)
    cpu = jnp.arange(c, dtype=jnp.int32)
    # a CPU's rank among the eligible CPUs of its core below it
    below = _on_core(rows.sib) & (cpu[:, None] < cpu)         # (P, C, C)
    rank = jnp.sum(below & eligible[:, :, None], axis=1, dtype=jnp.int32)
    intra = jnp.where(req.spread[:, None],
                      (rank * c + rows.core) * c + cpu,
                      rows.core * c + cpu)
    key = ((~eligible).astype(jnp.int32) << 28
           | (~sat).astype(jnp.int32) << 27
           | jnp.minimum(pool, 255) << 19
           | rows.numa << 16
           | intra)
    ranked = jnp.sort(key, axis=-1)
    nth = jnp.sum(jnp.where(cpu == jnp.clip(n - 1, 0, c - 1)[:, None],
                            ranked, 0), axis=-1, keepdims=True)
    ok = req.wants & (n >= 1) & (jnp.sum(eligible, axis=-1) >= n)
    return (key <= nth) & eligible & ok[:, None], ok


def apply_cpu_grants(cpus: CPUState, node: jnp.ndarray, keep: jnp.ndarray,
                     selection: jnp.ndarray,
                     req: PodCPURequests) -> CPUState:
    """Take the kept pods' grants off their nodes' CPUs."""
    take = (keep[:, None] & selection) * req.unit[:, None]
    return cpus.replace(
        held=cpus.held.at[jnp.where(keep, node, 0)].add(take))
